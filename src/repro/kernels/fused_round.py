"""One-launch fused EF round (Pallas TPU) — the whole client uplink and the
whole server downlink each collapse into a single kernel launch.

Uplink mega-kernel (``ef21_sgdm_topk_quant``), Algorithm 1 lines 6-8 plus the
wire codec in ONE HBM pass:

    v' = (1-eta)*v + eta*grad            momentum estimate
    c  = BlockTopK(v' - g)               threshold bisection, topk_compress.py
    (q, s) = quantize(c)                 per-block absmax, 8/4-bit mantissas
    g' = g + dequantize(q, s)            EF invariant: integrate the DECODE

The unfused path (ef_update.py -> topk_compress.py -> quantize.py) launches
three kernels and round-trips every intermediate (v', delta, c) through HBM —
~9 passes of d words for a phase that is purely memory-bound. Here every stage
lives in one VMEM tile: 3 reads (grad, v, g) + 2 f32 writes (v', g') + the
mantissa write at bits/32 of a word each.

Two contracts worth naming:

* **EF invariant.** ``g'`` integrates the dequantized wire, not the raw ``c``
  — what the client remembers must equal what the server decodes, otherwise
  the quantization error is never re-sent. The composed three-kernel path gets
  this for free only if the caller remembers to decode; the mega-kernel bakes
  it in.
* **Dense payload == sparse payload.** The quantization row is the selection
  block, so the masked row's absmax IS the absmax of the selected values, and
  masked-out zeros quantize to mantissa 0 exactly. Shipping the dense
  (nb, block) mantissa plane therefore decodes bit-identically to shipping the
  (vals, idx) sparse payload — no in-kernel compaction (TPU-hostile scatter)
  is needed to keep the wire faithful.

Non-finite grads are a client-side fault, not a supported input: the codec
guard keeps the wire and ``g'`` finite (non-finite entries decode to exactly
0), but the selection among a partially non-finite row is unspecified (the
bisection degenerates to keep-everything-finite).

Downlink kernel (``dequant_add``): dequantize + integrate in one launch,

    out = base + alpha * dequantize(q, s)

covering the EF21 broadcast-memory integration h' = h + decode(wire)
(alpha=1) and the fused model step x' = x - gamma*decode (alpha=-gamma).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import telemetry
from repro.kernels.quantize import (_pad_rows, _row_tiles, pack_nibbles,
                                    unpack_nibbles)
from repro.kernels.topk_compress import _bisect_threshold

UPLINK = "ef21_sgdm_uplink"


def _fused_uplink_kernel(grad_ref, v_ref, g_ref, v_out, g_out, q_out, s_out,
                         *, eta: float, k: int, bits: int):
    grad = grad_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    # lines 6-7: momentum estimate, innovation, Block-TopK selection
    v_new = v + eta * (grad - v)          # core/ef.py::tree_lerp's form
    delta = v_new - g
    ab = jnp.abs(delta)
    t = _bisect_threshold(ab, k)          # (rt, 1)
    c = jnp.where(ab >= t, delta, 0.0)
    # wire codec — same arithmetic as quantize._quant_kernel, one row per
    # selection block (the masked row's absmax is the selected values' absmax)
    c = jnp.where(jnp.isfinite(c), c, 0.0)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(c), axis=1, keepdims=True) / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(c / safe), -qmax, qmax)
    # line 8 under the EF invariant: g' integrates what the server decodes
    c_hat = q * scale
    v_out[...] = v_new.astype(v_out.dtype)
    g_out[...] = (g + c_hat).astype(g_out.dtype)
    s_out[...] = scale
    if bits == 8:
        q_out[...] = q.astype(jnp.int8)
    else:
        q_out[...] = pack_nibbles(q)


def ef21_sgdm_topk_quant(grad: jax.Array, v: jax.Array, g: jax.Array, *,
                         eta: float, block: int = 1024, k: int = 16,
                         bits: int = 8,
                         rows_per_tile: Optional[int] = None,
                         interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """All inputs the same shape. Returns ``(v', g', q, scales)`` where
    (q, scales) is the quantized wire of c at the selection geometry — q int8
    (nb, block) for bits=8, packed uint4 pairs (nb, block//2) for bits=4 —
    and g' = g + dequantize(q, scales) (the EF invariant, enforced in-kernel).

    The row tile is as tall as the per-operand VMEM budget allows
    (``quantize._row_tiles``: 128 rows at block 1024), ``rows_per_tile``
    an upper bound under it. The 26 bisection iterations are serially
    dependent and a tile's rows are the only independent work in each, so
    a short tile leaves every iteration waiting on its own cross-lane
    count (8 rows ran each grid step at 13x its HBM time on a v5e). Rows
    are independent, so the tile changes no output bit. Each launch's
    rows, tile and grid steps are recorded at trace time
    (``repro.telemetry.tiles``).
    """
    assert bits in (8, 4), bits
    assert bits == 8 or block % 2 == 0, "uint4 packing needs an even block"
    shape, d = grad.shape, grad.size
    nb = -(-d // block)
    rt, nbp = _row_tiles(nb, block, rows_per_tile)
    pad = nbp * block - d
    telemetry.record_tile(UPLINK, rows=nbp, block=block, rt=rt)

    def prep(x):
        return jnp.pad(x.reshape(-1), (0, pad)).reshape(nbp, block)

    qcols = block if bits == 8 else block // 2
    qdtype = jnp.int8 if bits == 8 else jnp.uint8
    spec = pl.BlockSpec((rt, block), lambda i: (i, 0))
    v_new, g_new, q, scales = pl.pallas_call(
        functools.partial(_fused_uplink_kernel, eta=eta, k=k, bits=bits),
        grid=(nbp // rt,),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec,
                   pl.BlockSpec((rt, qcols), lambda i: (i, 0)),
                   pl.BlockSpec((rt, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((nbp, block), v.dtype),
                   jax.ShapeDtypeStruct((nbp, block), g.dtype),
                   jax.ShapeDtypeStruct((nbp, qcols), qdtype),
                   jax.ShapeDtypeStruct((nbp, 1), jnp.float32)),
        interpret=interpret,
        name=UPLINK,
    )(prep(grad), prep(v), prep(g))

    def unprep(x):
        return x.reshape(-1)[:d].reshape(shape)

    return unprep(v_new), unprep(g_new), q[:nb], scales[:nb].reshape(-1)


def _dequant_add_kernel(q_ref, s_ref, b_ref, o_ref, *, bits: int,
                        alpha: float):
    scale = s_ref[...][:, 0]
    if bits == 8:
        vals = q_ref[...].astype(jnp.float32)
    else:
        vals = unpack_nibbles(q_ref[...])
    dec = vals * scale[:, None]
    if alpha != 1.0:
        dec = alpha * dec
    base = b_ref[...].astype(jnp.float32)
    o_ref[...] = (base + dec).astype(o_ref.dtype)


def dequant_add(q: jax.Array, scales: jax.Array, base: jax.Array, *, d: int,
                block: int = 256, bits: int = 8, alpha: float = 1.0,
                rows_per_tile: int = 8, interpret: bool = False) -> jax.Array:
    """``base + alpha * dequantize(q, scales)`` in one launch.

    ``base`` holds the first ``d`` of ``q``'s nb*block decoded slots (same flat
    layout as block_dequantize); returns an array of base's shape and dtype.
    The arithmetic is the oracle's f32 chain (dequantize then add), so the
    result is bit-identical to the two-step path.
    """
    assert bits in (8, 4), bits
    shape = base.shape
    rt, nbp = _row_tiles(q.shape[0], block, rows_per_tile)
    bb = jnp.pad(base.reshape(-1).astype(jnp.float32),
                 (0, nbp * block - d)).reshape(nbp, block)
    out = pl.pallas_call(
        functools.partial(_dequant_add_kernel, bits=bits, alpha=alpha),
        grid=(nbp // rt,),
        in_specs=[pl.BlockSpec((rt, q.shape[1]), lambda i: (i, 0)),
                  pl.BlockSpec((rt, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rt, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, block), base.dtype),
        interpret=interpret,
        name="dequant_add",
    )(_pad_rows(q, nbp), _pad_rows(scales.reshape(-1, 1), nbp), bb)
    return out.reshape(-1)[:d].reshape(shape)
