"""Shared transformer building blocks: RMSNorm, RoPE, GQA attention (full /
sliding-window banded / decode-with-cache), SwiGLU MLP.

Attention takes one of three paths, chosen by ``attn_apply`` from the call's
shape alone. Full causal self-attention with no softcap, no binding window,
``H % KV == 0`` and a sequence that the kernel's block divides (training and
cache-less forwards at S = 256, 512, ...) runs as one fused Pallas flash
kernel, forward and backward (``kernels/ops.py::causal_gqa_attention``):
compiled by Mosaic on a TPU, interpreted on the CPU. Every other
full-sequence call (prefill with a cache, a binding sliding window, a
softcap, short sequences) runs *chunked* attention: an outer ``lax.scan``
over query chunks keeps the HLO small and the live score tensor bounded at
``(B, H, chunk_q, S_kv)``; sliding-window layers use a *banded* schedule
(each query chunk attends only to a ``window + chunk`` KV slice → O(S·W)
FLOPs instead of O(S²)). Decoding one token attends to its cache in jnp.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.kernels import ops as kernel_ops

Array = jax.Array


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------

def shard(x: Array, *spec) -> Array:
    """Activation sharding hint; no-op when no mesh is active."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return x
        names = set(mesh.axis_names)
        spec = tuple(s if (s in names or s is None or isinstance(s, tuple)) else None
                     for s in spec)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(jax.sharding.get_mesh(), P(*spec)))
    except Exception:
        return x


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x: Array, cap: Optional[float]) -> Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: Array, positions: Array, theta: float = 10000.0,
         scale: Optional[float] = None) -> Array:
    """x: (..., S, H, hd); positions: (..., S) absolute positions. ``scale``
    multiplies the rotated x in f32, before its one cast back to x's dtype."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if scale is not None:
        out = out * scale
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention cores
# ---------------------------------------------------------------------------

def _gqa_scores(q: Array, k: Array, cap: Optional[float]) -> Array:
    """q: (B,Sq,KV,G,hd), k: (B,Skv,KV,hd) → scores (B,KV,G,Sq,Skv) in f32."""
    s = jnp.einsum("bqngd,bknd->bngqk", q.astype(jnp.float32) / (q.shape[-1] ** 0.5),
                   k.astype(jnp.float32))
    return softcap(s, cap)


def _gqa_out(p: Array, v: Array) -> Array:
    """p: (B,KV,G,Sq,Skv), v: (B,Skv,KV,hd) → (B,Sq,KV*G,hd)."""
    o = jnp.einsum("bngqk,bknd->bqngd", p, v)  # (B,Sq,KV,G,hd)
    B, Sq, KV, G, hd = o.shape
    return o.reshape(B, Sq, KV * G, hd)


def chunked_attention(q: Array, k: Array, v: Array, *, chunk: int = 512,
                      window: Optional[int] = None, cap: Optional[float] = None,
                      q_offset: int = 0) -> Array:
    """Causal GQA attention. q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd).

    window=None → full causal (scores for one q-chunk vs full KV, masked).
    window=W    → banded: each q-chunk sees a (W + chunk)-wide KV slice.
    q_offset: absolute position of q[0] relative to k[0] (prefill: 0).
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    chunk = min(chunk, Sq)
    nq = -(-Sq // chunk)
    pad = nq * chunk - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q5 = qp.reshape(B, nq, chunk, KV, G, hd)

    kv_pos = jnp.arange(Skv)

    # NB: masks are applied as low-rank additive f32 biases (never rank-of-scores
    # predicates): XLA hoists loop-invariant masks out of the q-chunk scan, and a
    # broadcast pred at score rank would materialize O(nq·B·H·cq·Skv) bytes.
    if window is None:
        def body(_, qi_i):
            qi, i = qi_i
            q_pos = q_offset + i * chunk + jnp.arange(chunk)
            s = _gqa_scores(qi, k, cap)                       # (B,KV,G,cq,Skv)
            bias = jnp.where(kv_pos[None, :] <= q_pos[:, None], 0.0, -1e30)
            p = jax.nn.softmax(s + bias[None, None, None], axis=-1).astype(v.dtype)
            return 0, _gqa_out(p, v)
        body = jax.checkpoint(body)   # flash-style: recompute probs in backward
        _, outs = jax.lax.scan(body, 0, (q5.swapaxes(0, 1), jnp.arange(nq)))
    else:
        ws = min(window + chunk, Skv)

        def body(_, qi_i):
            qi, i = qi_i
            q_pos = q_offset + i * chunk + jnp.arange(chunk)
            start = jnp.clip(q_offset + (i + 1) * chunk - ws, 0, Skv - ws)
            ks = jax.lax.dynamic_slice_in_dim(k, start, ws, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, ws, axis=1)
            k_pos = start + jnp.arange(ws)
            s = _gqa_scores(qi, ks, cap)
            bias = jnp.where((k_pos[None, :] <= q_pos[:, None])
                             & (k_pos[None, :] > q_pos[:, None] - window),
                             0.0, -1e30)
            p = jax.nn.softmax(s + bias[None, None, None], axis=-1).astype(v.dtype)
            return 0, _gqa_out(p, vs)
        body = jax.checkpoint(body)   # flash-style: recompute probs in backward
        _, outs = jax.lax.scan(body, 0, (q5.swapaxes(0, 1), jnp.arange(nq)))

    out = outs.swapaxes(0, 1).reshape(B, nq * chunk, H, hd)
    return out[:, :Sq]


def decode_attention(q: Array, k_cache: Array, v_cache: Array, pos: Array, *,
                     window: Optional[int] = None,
                     cap: Optional[float] = None) -> Array:
    """One-token attention against a cache.

    q: (B,1,H,hd); caches: (B,S,KV,hd). ``pos`` is the absolute position of the new
    token. Full caches store position p at slot p; sliding-window caches are ring
    buffers of size W storing position p at slot p mod W.
    """
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    s = _gqa_scores(q.reshape(B, 1, KV, H // KV, hd), k_cache, cap)  # (B,KV,G,1,S)
    slot = jnp.arange(S)
    if window is None:
        valid = slot <= pos
    else:
        valid = (slot <= pos) | (pos >= S)      # ring buffer: all slots once full
    bias = jnp.where(valid, 0.0, -1e30)
    p = jax.nn.softmax(s + bias[None, None, None, None, :],
                       axis=-1).astype(v_cache.dtype)
    return _gqa_out(p, v_cache).reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# attention block (params + apply)
# ---------------------------------------------------------------------------

def attn_init(rng, d: int, H: int, KV: int, hd: int, dtype,
              h_eff: Optional[int] = None, kv_eff: Optional[int] = None) -> dict:
    """h_eff/kv_eff > H/KV → TP head padding (MHA-expand): kv head j//G is
    replicated under query head j (< H); padded q heads get zero wo rows, so
    the function is EXACTLY that of the unpadded layer."""
    h_eff = h_eff or H
    kv_eff = kv_eff or KV
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    sd = d ** -0.5
    wq = (jax.random.normal(k1, (d, h_eff, hd)) * sd).astype(dtype)
    wo = (jax.random.normal(k4, (h_eff, hd, d)) * (H * hd) ** -0.5).astype(dtype)
    if kv_eff == KV:
        wk = (jax.random.normal(k2, (d, KV, hd)) * sd).astype(dtype)
        wv = (jax.random.normal(k3, (d, KV, hd)) * sd).astype(dtype)
    else:
        assert kv_eff == h_eff, "MHA-expand pads kv to the q-head count"
        G = H // KV
        base_k = jax.random.normal(k2, (d, KV, hd)) * sd
        base_v = jax.random.normal(k3, (d, KV, hd)) * sd
        idx = jnp.minimum(jnp.arange(h_eff) // G, KV - 1)
        pad_mask = (jnp.arange(h_eff) < H)[None, :, None]
        wk = (base_k[:, idx] * pad_mask).astype(dtype)
        wv = (base_v[:, idx] * pad_mask).astype(dtype)
        wo = wo * pad_mask.reshape(h_eff, 1, 1)     # zero rows for padded heads
    return {"wq": wq, "wk": wk, "wv": wv, "wo": wo,
            "norm": jnp.zeros((d,), dtype)}


def attn_apply(p: dict, x: Array, positions: Array, *, rope_theta: float,
               eps: float, chunk: int, window: Optional[int] = None,
               cap: Optional[float] = None,
               cache: Optional[Tuple[Array, Array]] = None,
               pos_scalar: Optional[Array] = None,
               ) -> Tuple[Array, Optional[Tuple[Array, Array]]]:
    """Pre-norm attention sub-block. Returns (residual_delta, new_cache).

    Modes:
      cache is None               → train/prefill without cache output
      cache=(k,v), x has S tokens → prefill: fill slots [0,S)
      cache=(k,v), x has 1 token  → decode at ``pos_scalar``
    """
    h = rms_norm(x, p["norm"], eps)
    q = jnp.einsum("bsd,dnh->bsnh", h, p["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dnh->bsnh", h, p["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dnh->bsnh", h, p["wv"].astype(h.dtype))
    q = shard(q, None, None, "model", None)
    B, S, H, hd = q.shape
    KV = k.shape[2]
    # the fused kernel: full causal self-attention (no cache, so q and k are
    # the same positions) that no softcap or window changes, whole blocks
    block = None
    if cache is None and cap is None and (window is None or window >= S) \
            and H % KV == 0:
        block = kernel_ops.causal_attention_block(S)
    # the kernel takes q already scaled: fold 1/sqrt(hd) in before q's one
    # rounding to the compute dtype
    q = rope(q, positions, rope_theta,
             scale=None if block is None else hd ** -0.5)
    k = rope(k, positions, rope_theta)
    with jax.named_scope(telemetry.ATTENTION):
        out, new_cache = _attend(q, k, v, block, chunk=chunk, window=window,
                                 cap=cap, cache=cache, pos_scalar=pos_scalar)
    out = shard(out, None, None, "model", None)
    return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(out.dtype)), new_cache


def _attend(q: Array, k: Array, v: Array, block: Optional[int], *,
            chunk: int, window: Optional[int], cap: Optional[float],
            cache: Optional[Tuple[Array, Array]], pos_scalar: Optional[Array],
            ) -> Tuple[Array, Optional[Tuple[Array, Array]]]:
    """``attn_apply``'s attention core on roped q, k, v: the fused kernel
    where ``block`` is set, else decode against the cache or chunked
    attention (filling the cache on prefill). Records the path taken."""
    B, Sq, H, hd = q.shape
    shape = dict(B=B, S=Sq, H=H, KV=k.shape[2], hd=hd)
    if block is not None:
        telemetry.record_attention("kernel", block=block, **shape)
        return kernel_ops.causal_gqa_attention(q, k, v), None

    new_cache = None
    if cache is not None and Sq == 1:
        telemetry.record_attention("decode", **shape)
        kc, vc = cache
        S = kc.shape[1]
        slot = pos_scalar if window is None else pos_scalar % S
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), slot, axis=1)
        out = decode_attention(q, kc, vc, pos_scalar, window=window, cap=cap)
        new_cache = (kc, vc)
    else:
        telemetry.record_attention("chunked", **shape)
        out = chunked_attention(q, k, v, chunk=chunk, window=window, cap=cap)
        if cache is not None:     # prefill: write the (possibly windowed) tail
            kc, vc = cache
            S = kc.shape[1]
            if window is not None and Sq > S:
                # keep last W keys; ring-buffer alignment: slot p mod S
                tail_k, tail_v = k[:, -S:], v[:, -S:]
                roll = (Sq % S)
                kc = jnp.roll(tail_k.astype(kc.dtype), roll, axis=1)
                vc = jnp.roll(tail_v.astype(vc.dtype), roll, axis=1)
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(
                    kc, k[:, :S].astype(kc.dtype), 0, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(
                    vc, v[:, :S].astype(vc.dtype), 0, axis=1)
            new_cache = (kc, vc)
    return out, new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(rng, d: int, ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w_gate": (jax.random.normal(k1, (d, ff)) * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(k2, (d, ff)) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(k3, (ff, d)) * ff ** -0.5).astype(dtype),
        "norm": jnp.zeros((d,), dtype),
    }


def mlp_apply(p: dict, x: Array, eps: float) -> Array:
    h = rms_norm(x, p["norm"], eps)
    g = jnp.einsum("bsd,df->bsf", h, p["w_gate"].astype(h.dtype))
    u = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(h.dtype))
    g = shard(g, None, None, "model")
    out = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", out, p["w_down"].astype(out.dtype))
