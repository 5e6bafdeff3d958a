"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """q,k,v: (B, S, H, hd) (same head counts — GQA expansion done by caller).
    Plain materialized-softmax attention in f32."""
    B, S, H, hd = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (hd ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def block_topk_ref(x: jax.Array, block: int, k: int) -> jax.Array:
    """Block-TopK sparsification: within each contiguous `block`, zero everything
    but the k largest-|·| entries (ties keep the earliest index, matching the
    kernel's >threshold-and-capacity rule)."""
    d = x.size
    nb = -(-d // block)
    xb = jnp.pad(x.reshape(-1), (0, nb * block - d)).reshape(nb, block)
    ab = jnp.abs(xb)
    # exact top-k with deterministic tie-break by index (earlier wins)
    order = jnp.argsort(-ab, axis=1, stable=True)
    ranks = jnp.argsort(order, axis=1, stable=True)
    out = jnp.where(ranks < k, xb, 0.0)
    return out.reshape(-1)[:d].reshape(x.shape)


def ef21_sgdm_update_ref(grad: jax.Array, v: jax.Array, g: jax.Array, *,
                         eta: float, block: int, k: int
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused EF21-SGDM client update (Algorithm 1 lines 6–8) with Block-TopK:
       v' = (1−η)v + η·grad;  c = BlockTopK(v' − g);  g' = g + c.
    Returns (v', g', c)."""
    v_new = v + eta * (grad - v)          # core/ef.py::tree_lerp's form
    c = block_topk_ref(v_new - g, block, k)
    return v_new, g + c, c


def ef21_sgdm_topk_quant_ref(grad: jax.Array, v: jax.Array, g: jax.Array, *,
                             eta: float, block: int, k: int, bits: int
                             ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                        jax.Array]:
    """Composed oracle for the fused uplink mega-kernel
    (kernels/fused_round.py): block_quantize_ref ∘ block_topk_ref ∘
    ef21_sgdm_update_ref, then g' integrates the DECODE of the wire (the EF
    invariant — what the client remembers must equal what the server reads).
    Returns (v', g', q, scales) on the same flat-block layout as the kernel."""
    shape, d = grad.shape, grad.size
    nb = -(-d // block)
    v_new, _, c = ef21_sgdm_update_ref(grad, v, g, eta=eta, block=block, k=k)
    cb = jnp.pad(c.reshape(-1).astype(jnp.float32),
                 (0, nb * block - d)).reshape(nb, block)
    q, scales = block_quantize_ref(cb, bits)
    c_hat = block_dequantize_ref(q, scales, bits=bits,
                                 cols=block).reshape(-1)[:d].reshape(shape)
    g_new = (g.astype(jnp.float32) + c_hat).astype(g.dtype)
    return v_new, g_new, q, scales


def block_quantize_ref(x: jax.Array, bits: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """Per-row absmax quantization of a (rows, cols) array — each row is one
    quantization block. Symmetric signed grid: scale = absmax/(2^(bits−1)−1),
    q = round(x/scale) ∈ [−qmax, qmax]. bits=8 stores int8 mantissas; bits=4
    packs two uint4 mantissas (offset by +8) per uint8 byte, odd cols padded.
    Non-finite inputs are treated as 0 (the scale stays finite; they decode to
    exactly 0 — EF then re-sends that mass as ordinary residual).
    A zero row gets scale 0 and decodes to exact zeros.
    Returns (q, scales): q int8 (rows, cols) | uint8 (rows, ceil(cols/2)),
    scales f32 (rows,)."""
    x = x.astype(jnp.float32)
    x = jnp.where(jnp.isfinite(x), x, 0.0)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x), axis=1) / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe[:, None]), -qmax, qmax)
    if bits == 8:
        return q.astype(jnp.int8), scale
    if q.shape[1] % 2:
        q = jnp.pad(q, ((0, 0), (0, 1)))
    # even/odd column slices, never a trailing axis of 2: on TPU such an
    # axis is padded to a whole 128-lane tile, 64x the bytes
    u = (q + 8.0).astype(jnp.uint8)
    return (u[:, 0::2] << 4) | u[:, 1::2], scale


def block_dequantize_ref(q: jax.Array, scales: jax.Array, *, bits: int,
                         cols: int) -> jax.Array:
    """Inverse of :func:`block_quantize_ref`: q·scale per row, f32 (rows, cols)."""
    if bits == 8:
        vals = q.astype(jnp.float32)
    else:
        # each byte repeated over its two slots, then the high nibble in even
        # slots and the low one in odd slots (no trailing axis of 2 — see
        # block_quantize_ref)
        pairs = jnp.repeat(q.astype(jnp.int32), 2, axis=1)
        shift = jnp.where(jnp.arange(pairs.shape[1]) % 2 == 0, 4, 0)
        vals = ((pairs >> shift) & 0xF)[:, :cols].astype(jnp.float32) - 8.0
    return vals * scales.astype(jnp.float32)[:, None]
