"""Block-TopK sparsification via in-kernel threshold bisection (Pallas TPU).

TPU adaptation of the paper's TopK compressor (DESIGN.md §4): global sort-based
selection is MXU/VPU-hostile, so we select *within* VMEM-tile-sized blocks using
~24 iterations of threshold bisection on |x| — each iteration is a fully
vectorized count-compare over the tile (VPU-friendly), no sort anywhere.

Exactness: bisection on float32 magnitudes converges to the k-th largest |x| to
~2⁻²⁴ relative precision; the emitted mask keeps entries with |x| ≥ threshold.
With distinct magnitudes this is exactly Block-TopK; exact ties at the threshold
are all kept (error only shrinks; the contraction bound α = k/block still holds).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize import _row_tiles

BISECT_ITERS = 26


def _bisect_threshold(ab: jax.Array, k: int) -> jax.Array:
    """ab: (rows, block) |values|. Returns the (rows, 1) per-row threshold t
    s.t. count(ab >= t) >= k and t is (approximately) maximal.

    The per-row state stays a (rows, 1) column from the max to the last
    count, so no iteration moves it between a lane-reduced column and a 1-D
    vector and back: each iteration is compare, count along the lanes and
    two selects, and the rows of a tile are its only independent work."""
    hi = jnp.max(ab, axis=1, keepdims=True)       # count(>=hi) >= 1
    lo = jnp.zeros_like(hi)                       # count(>=0)  = block >= k

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((ab >= mid).astype(jnp.int32), axis=1, keepdims=True)
        ok = cnt >= k                             # mid keeps enough → raise lo
        lo = jnp.where(ok, mid, lo)
        hi = jnp.where(ok, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    return lo


def _topk_kernel(x_ref, o_ref, *, k: int):
    x = x_ref[...].astype(jnp.float32)            # (rows, block)
    ab = jnp.abs(x)
    t = _bisect_threshold(ab, k)
    o_ref[...] = jnp.where(ab >= t, x, 0.0).astype(o_ref.dtype)


def block_topk(x: jax.Array, *, block: int = 1024, k: int = 16,
               rows_per_tile: int = 8, interpret: bool = False) -> jax.Array:
    """x: any shape; flattened, padded to blocks, sparsified, reshaped back."""
    shape, d = x.shape, x.size
    rt, nb = _row_tiles(-(-d // block), block, rows_per_tile)
    xb = jnp.pad(x.reshape(-1), (0, nb * block - d)).reshape(nb, block)

    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(nb // rt,),
        in_specs=[pl.BlockSpec((rt, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), x.dtype),
        interpret=interpret,
        name="topk_compress",
    )(xb)
    return out.reshape(-1)[:d].reshape(shape)
