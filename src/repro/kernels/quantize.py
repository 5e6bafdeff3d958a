"""Block-quantize / dequantize (Pallas TPU) — the wire codec of the quantized
carriers (core/carriers.py::QuantCarrier).

One grid step quantizes a tile of rows: each row is an independent
quantization block (per-row absmax scale + int8 or packed-uint4 mantissas).
Everything is elementwise + a per-row max, so the kernel is purely
VPU/memory-bound: on TPU it streams the f32 input once and writes mantissas at
1/4 (int8) or 1/8 (uint4) of the input bytes. Deterministic round-to-nearest —
bit-identical to the pure-jnp oracle (kernels/ref.py::block_quantize_ref),
which is what the carriers run under vmap (no vmap-of-pallas_call is ever
emitted; the unbatched shard_map encode path calls the kernel directly).

Guards (same contract as the oracle): non-finite inputs quantize to exactly 0
with a finite scale; an all-zero block gets scale 0 and decodes to exact zeros.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref, *, bits: int):
    x = x_ref[...].astype(jnp.float32)
    x = jnp.where(jnp.isfinite(x), x, 0.0)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x), axis=1) / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe[:, None]), -qmax, qmax)
    s_ref[...] = scale[:, None]
    if bits == 8:
        q_ref[...] = q.astype(jnp.int8)
    else:
        q_ref[...] = pack_nibbles(q)


def _dequant_kernel(q_ref, s_ref, o_ref, *, bits: int):
    scale = s_ref[...][:, 0]
    if bits == 8:
        vals = q_ref[...].astype(jnp.float32)
    else:
        vals = unpack_nibbles(q_ref[...])
    o_ref[...] = (vals * scale[:, None]).astype(o_ref.dtype)


# Lane width of one packing matmul: 256 mantissas in, 128 bytes out.
_PACK_LANES = 256


def pack_nibbles(q: jax.Array) -> jax.Array:
    """(rows, block) f32 4-bit mantissas in [-7, 7] → (rows, block//2)
    uint8, byte j = (q[2j] + 8) << 4 | (q[2j+1] + 8) — the wire layout of
    kernels/ref.py::block_quantize_ref.

    Mosaic refuses the even/odd lane split as a reshape, so the split runs
    on the MXU: each 256-lane chunk times a 0/16/1 pairing matrix gives
    16·u[2j] + u[2j+1] directly. Exact: the operands are integers ≤ 16,
    exact in bf16, and each output sums two products in f32. The only
    narrowing cast goes through int32, which Mosaic accepts."""
    rows, block = q.shape
    w = _PACK_LANES if block % _PACK_LANES == 0 else block
    r = jax.lax.broadcasted_iota(jnp.int32, (w, w // 2), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (w, w // 2), 1)
    pair = jnp.where(r == 2 * c, 16.0,
                     jnp.where(r == 2 * c + 1, 1.0, 0.0)).astype(jnp.bfloat16)
    u = (q + 8.0).astype(jnp.bfloat16)
    parts = [jnp.dot(u[:, i:i + w], pair, preferred_element_type=jnp.float32)
             for i in range(0, block, w)]
    packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return packed.astype(jnp.int32).astype(jnp.uint8)


def unpack_nibbles(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_nibbles`: (rows, n) uint8 → (rows, 2n) f32
    mantissas. Widened to int32 first (Mosaic has no uint8 → f32 cast)."""
    p = p.astype(jnp.int32)
    hi = (p >> 4).astype(jnp.float32) - 8.0
    lo = (p & 0xF).astype(jnp.float32) - 8.0
    return jnp.stack([hi, lo], axis=-1).reshape(p.shape[0], -1)


# Per-operand VMEM budget for one grid step, in f32 words. A kernel holds a
# handful of (rt, block) tiles live at once (inputs + outputs + intermediates
# like |x| in the bisection), so 128Ki words ≈ 512 KB/operand keeps the worst
# case (~6 operands) comfortably inside the ~16 MB/core VMEM.
_VMEM_TILE_WORDS = 1 << 17


# Mosaic tiles a block's rows in whole 8-row sublane tiles, and VMEM holds
# its last axis in whole 128-lane vregs.
_SUBLANES, _LANES = 8, 128


def _row_tiles(nb: int, block: int, rows_per_tile: Optional[int] = 8
               ) -> Tuple[int, int]:
    """``(rt, nbp)``: rows per grid step, and the row count padded up to a
    whole number of steps.

    ``rows_per_tile`` is an upper bound (None: none), further capped so one
    operand tile (rt × block f32, the block rounded up to whole 128-lane
    vregs as VMEM holds it) stays within the per-operand VMEM budget — a
    lane-rounded single-block leaf can make ``block`` itself huge, and a
    narrow block still fills whole lanes. Mosaic takes a block whose row
    count is a multiple of 8 or the whole array: an array of at most one
    tile runs as one step, a longer one steps in multiples of 8 rows, with
    zero rows appended when no such tile divides it. Every kernel here is
    row-independent, so the padding rows never touch a real row; the
    callers slice them off. Lane alignment is the 128-wide last axis, which
    the callers own — this helper only sizes the row axis.
    """
    lanes = -(-max(1, block) // _LANES) * _LANES
    cap = max(1, _VMEM_TILE_WORDS // lanes)
    rt = cap if rows_per_tile is None else max(1, min(rows_per_tile, cap))
    if nb <= rt:
        return nb, nb
    if rt >= _SUBLANES:
        rt -= rt % _SUBLANES
        for cand in range(rt, 0, -_SUBLANES):
            if nb % cand == 0:
                return cand, nb
    return rt, -(-nb // rt) * rt


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    """Append zero rows to a 2-D array up to ``rows``."""
    if x.shape[0] == rows:
        return x
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))


def block_quantize(x: jax.Array, *, block: int = 256, bits: int = 8,
                   rows_per_tile: int = 8, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """x: any shape, flattened and zero-padded to whole blocks. Returns
    (q, scales): q int8 (nb, block) for bits=8, uint8 (nb, block//2) packed
    uint4 pairs for bits=4 (block must be even), scales f32 (nb,)."""
    assert bits in (8, 4), bits
    assert bits == 8 or block % 2 == 0, "uint4 packing needs an even block"
    d = x.size
    nb = -(-d // block)
    rt, nbp = _row_tiles(nb, block, rows_per_tile)
    xb = jnp.pad(x.reshape(-1), (0, nbp * block - d)).reshape(nbp, block)
    qcols = block if bits == 8 else block // 2
    qdtype = jnp.int8 if bits == 8 else jnp.uint8

    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, bits=bits),
        grid=(nbp // rt,),
        in_specs=[pl.BlockSpec((rt, block), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((rt, qcols), lambda i: (i, 0)),
                   pl.BlockSpec((rt, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((nbp, qcols), qdtype),
                   jax.ShapeDtypeStruct((nbp, 1), jnp.float32)),
        interpret=interpret,
        name="block_quantize",
    )(xb)
    return q[:nb], scales[:nb].reshape(-1)


def block_dequantize(q: jax.Array, scales: jax.Array, *, d: int,
                     block: int = 256, bits: int = 8, rows_per_tile: int = 8,
                     interpret: bool = False) -> jax.Array:
    """Inverse of :func:`block_quantize`; returns the flat (d,) f32 decode."""
    assert bits in (8, 4), bits
    rt, nbp = _row_tiles(q.shape[0], block, rows_per_tile)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, bits=bits),
        grid=(nbp // rt,),
        in_specs=[pl.BlockSpec((rt, q.shape[1]), lambda i: (i, 0)),
                  pl.BlockSpec((rt, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, block), jnp.float32),
        interpret=interpret,
        name="block_dequantize",
    )(_pad_rows(q, nbp), _pad_rows(scales.reshape(-1, 1), nbp))
    return out.reshape(-1)[:d]
