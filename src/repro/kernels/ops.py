"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels run in the Pallas interpreter (the kernel body
op-for-op — the correctness target validated against ref.py); on a TPU they
compile to Mosaic. ``kernels/backend.py::interpret`` makes that choice.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax

from repro.kernels import ef_update as _ef
from repro.kernels.backend import interpret as _interpret
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_round as _fr
from repro.kernels import quantize as _qz
from repro.kernels import topk_compress as _tk


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_k: int = 256) -> jax.Array:
    """(B,S,H,hd) attention; GQA callers expand kv heads first."""
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block", "k"))
def block_topk(x, *, block: int = 1024, k: int = 16) -> jax.Array:
    return _tk.block_topk(x, block=block, k=k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eta", "block", "k"))
def ef21_sgdm_update(grad, v, g, *, eta: float, block: int = 1024,
                     k: int = 16) -> Tuple[jax.Array, jax.Array, jax.Array]:
    return _ef.ef21_sgdm_update(grad, v, g, eta=eta, block=block, k=k,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eta", "block", "k", "bits"))
def ef21_sgdm_topk_quant(grad, v, g, *, eta: float, block: int = 1024,
                         k: int = 16, bits: int = 8
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """One-launch uplink: EF update + BlockTopK + quantize → (v', g', q, s)."""
    return _fr.ef21_sgdm_topk_quant(grad, v, g, eta=eta, block=block, k=k,
                                    bits=bits, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("d", "block", "bits", "alpha"))
def dequant_add(q, scales, base, *, d: int, block: int = 256, bits: int = 8,
                alpha: float = 1.0) -> jax.Array:
    """One-launch downlink: base + alpha·dequantize(q, scales)."""
    return _fr.dequant_add(q, scales, base, d=d, block=block, bits=bits,
                           alpha=alpha, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block", "bits"))
def block_quantize(x, *, block: int = 256,
                   bits: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Per-block absmax wire quantization → (mantissas, scales)."""
    return _qz.block_quantize(x, block=block, bits=bits,
                              interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("d", "block", "bits"))
def block_dequantize(q, scales, *, d: int, block: int = 256,
                     bits: int = 8) -> jax.Array:
    return _qz.block_dequantize(q, scales, d=d, block=block, bits=bits,
                                interpret=_interpret())
