"""Fused EF21-SGDM client update (Pallas TPU) — Algorithm 1 lines 6–8 in ONE
HBM pass:

    v' = (1−η)·v + η·grad
    c  = BlockTopK(v' − g)        (threshold bisection, see topk_compress.py)
    g' = g + c

The unfused update reads/writes each of (grad, v, g, δ, c, g') separately — ~9
HBM passes of d words; the optimizer phase of EF training is purely memory-bound,
so fusion is a direct ~3× on its memory-roofline term (§Perf). All arithmetic is
elementwise + the bisection counts; everything lives in one VMEM tile.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize import _row_tiles
from repro.kernels.topk_compress import _bisect_threshold


def _ef_kernel(grad_ref, v_ref, g_ref, v_out, g_out, c_out, *,
               eta: float, k: int):
    grad = grad_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v_new = v + eta * (grad - v)          # core/ef.py::tree_lerp's form
    delta = v_new - g
    ab = jnp.abs(delta)
    t = _bisect_threshold(ab, k)
    c = jnp.where(ab >= t, delta, 0.0)
    v_out[...] = v_new.astype(v_out.dtype)
    g_out[...] = (g + c).astype(g_out.dtype)
    c_out[...] = c.astype(c_out.dtype)


def ef21_sgdm_update(grad: jax.Array, v: jax.Array, g: jax.Array, *,
                     eta: float, block: int = 1024, k: int = 16,
                     rows_per_tile: int = 8, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All inputs same shape. Returns (v', g', c)."""
    shape, d = grad.shape, grad.size
    rt, nb = _row_tiles(-(-d // block), block, rows_per_tile)
    pad = nb * block - d

    def prep(x):
        return jnp.pad(x.reshape(-1), (0, pad)).reshape(nb, block)

    spec = pl.BlockSpec((rt, block), lambda i: (i, 0))
    v_new, g_new, c = pl.pallas_call(
        functools.partial(_ef_kernel, eta=eta, k=k),
        grid=(nb // rt,),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec, spec),
        out_shape=tuple(jax.ShapeDtypeStruct((nb, block), x.dtype)
                        for x in (v, g, g)),
        interpret=interpret,
        name="ef_update",
    )(prep(grad), prep(v), prep(g))

    def unprep(x):
        return x.reshape(-1)[:d].reshape(shape)

    return unprep(v_new), unprep(g_new), unprep(c)
