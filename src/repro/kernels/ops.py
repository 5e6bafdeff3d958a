"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels run in the Pallas interpreter (the kernel body
op-for-op — the correctness target validated against ref.py); on a TPU they
compile to Mosaic. ``kernels/backend.py::interpret`` makes that choice.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
    splash_attention_mask as _splash_mask,
)

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


# Causal GQA attention's block edge, one size for the query and key blocks
# of its forward, dK/dV and dQ passes: the first of these that divides the
# sequence.
ATTENTION_BLOCKS = (512, 256)


def causal_attention_block(S: int) -> Optional[int]:
    """The block :func:`causal_gqa_attention` tiles a sequence of ``S``
    tokens by, or None where none divides ``S``."""
    return next((b for b in ATTENTION_BLOCKS if S % b == 0), None)


def _causal_mqa_kernel(S: int, G: int, block: int, interpret: bool):
    """The bundled splash kernel (a forward kernel, and dQ and dK/dV
    backward kernels under its custom VJP) for ``G`` query heads sharing
    one key/value head, with a causal mask whose wholly-future blocks are
    skipped. Built anew for each trace: its block tables become constants
    of the caller's program, and splash caches the mask's processing
    itself, so no device array outlives the trace."""
    sizes = _splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    mask = _splash_mask.MultiHeadMask(
        [_splash_mask.CausalMask((S, S))] * G)
    return _splash.make_splash_mqa_single_device(
        mask, block_sizes=sizes, interpret=interpret)


def causal_gqa_attention(q, k, v) -> jax.Array:
    """Causal GQA attention as one fused flash kernel, forward and backward:
    scores and probabilities stay in VMEM. ``q``: (B,S,H,hd), already
    scaled by 1/sqrt(hd); ``k``, ``v``: (B,S,KV,hd) with ``H % KV == 0``.
    Operands stay in their dtype (bf16 on the MXU, accumulated in f32);
    the block is :func:`causal_attention_block`'s.

    Under a mesh, Mosaic cannot partition the kernel, so each chip runs it
    on its own shard inside a ``shard_map`` manual over every axis: heads
    split over ``model`` where it divides both head counts, every other
    dimension whole. The batch is split by the caller's ``vmap`` over
    clients (``spmd_axis_name``, ``core/distributed.py``); a batch that no
    such vmap splits is gathered whole onto each chip."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return _causal_gqa(q, k, v)
    H, KV = q.shape[2], k.shape[2]
    # naming an axis in the specs, even one of size 1, spares the backward
    # a sum over it
    tp = mesh.shape.get("model")
    heads = "model" if tp and H % tp == 0 and KV % tp == 0 else None
    spec = P(None, None, heads, None)
    return jax.shard_map(_causal_gqa, in_specs=(spec,) * 3, out_specs=spec,
                         axis_names=frozenset(mesh.axis_names),
                         check_vma=False)(q, k, v)


def _causal_gqa(q, k, v) -> jax.Array:
    """:func:`causal_gqa_attention` on one chip's operands."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    block = causal_attention_block(S)
    if block is None or H % KV:
        raise ValueError(f"no causal GQA kernel for S={S}, H={H}, KV={KV}")
    kernel = _causal_mqa_kernel(S, G, block, _interpret())
    qh = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
    kh, vh = (t.transpose(0, 2, 1, 3) for t in (k, v))
    o = jax.vmap(jax.vmap(kernel))(qh, kh, vh)         # (B,KV,G,S,hd)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)


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
