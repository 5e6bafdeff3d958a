"""Plain dense decoder: embedding, pre-norm grouped-query attention with
rotary positions, SwiGLU, a tied output head and mean cross-entropy.

Written from the published Llama-family equations in straightforward
``jax.numpy``, one sequence at a time, with float32 parameters and
activations; every matrix product runs at ``precision="highest"``. Where
the program under test departs from the published models it is matched
here and named: RMSNorm scales are stored as ``1 + w`` with ``w``
initialised to 0, the rotary embedding rotates the two halves of each head
(``rotate_half``), and the head is tied to the embedding.

``matmul_dtype`` rounds the operands of every matrix product to a lower
precision in the forward pass (the backward pass sees the rounded operands
and float32 cotangents). It is the control of the correctness check: the
reference computed one precision below the configuration's."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    if dtype is None:
        return x
    # forward rounds, backward passes the cotangent through unchanged
    return x + jax.lax.stop_gradient(x.astype(dtype).astype(x.dtype) - x)


def _mm(spec: str, a, b, dtype):
    return jnp.einsum(spec, _round(a, dtype), _round(b, dtype),
                      precision=HIGHEST)


def init(cfg: dict, key) -> dict:
    """Seeded weights by the program's initialisation recipe."""
    d, H, KV, hd, ff, V, L = (cfg["d_model"], cfg["num_heads"],
                              cfg["num_kv_heads"], cfg["head_dim"],
                              cfg["d_ff"], cfg["vocab_size"],
                              cfg["num_layers"])
    k_embed, k_layers, _ = jax.random.split(key, 3)
    normal = jax.random.normal

    def layer(k):
        k_attn, k_mlp = jax.random.split(k)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        kg, ku, kd = jax.random.split(k_mlp, 3)
        return {
            "attn": {"wq": normal(kq, (d, H, hd)) * d ** -0.5,
                     "wk": normal(kk, (d, KV, hd)) * d ** -0.5,
                     "wv": normal(kv, (d, KV, hd)) * d ** -0.5,
                     "wo": normal(ko, (H, hd, d)) * (H * hd) ** -0.5,
                     "norm": jnp.zeros((d,))},
            "mlp": {"w_gate": normal(kg, (d, ff)) * d ** -0.5,
                    "w_up": normal(ku, (d, ff)) * d ** -0.5,
                    "w_down": normal(kd, (ff, d)) * ff ** -0.5,
                    "norm": jnp.zeros((d,))},
        }

    return {"embed": normal(k_embed, (V, d)) * d ** -0.5,
            "final_norm": jnp.zeros((d,)),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, L))}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """x (S, heads, hd): rotate the two halves of each head."""
    S, _, hd = x.shape
    freq = theta ** -(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _layer(cfg, p, h, dt):
    S = h.shape[0]
    H, KV, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    a = p["attn"]
    x = _rms(h, a["norm"], eps)
    q = _rope(_mm("sd,dnh->snh", x, a["wq"], dt), theta)
    k = _rope(_mm("sd,dnh->snh", x, a["wk"], dt), theta)
    v = _mm("sd,dnh->snh", x, a["wv"], dt)
    q = q.reshape(S, KV, H // KV, hd)
    s = _mm("qngh,knh->ngqk", q, k, dt) / hd ** 0.5
    i = jnp.arange(S)
    keep = i[None, :] <= i[:, None]
    if cfg.get("sliding_window"):
        keep &= i[None, :] > i[:, None] - cfg["sliding_window"]
    s = jnp.where(keep, s, -jnp.inf)
    o = _mm("ngqk,knh->qngh", jax.nn.softmax(s, -1), v, dt)
    h = h + _mm("snh,nhd->sd", o.reshape(S, H, hd), a["wo"], dt)
    m = p["mlp"]
    x = _rms(h, m["norm"], eps)
    u = jax.nn.silu(_mm("sd,df->sf", x, m["w_gate"], dt)) \
        * _mm("sd,df->sf", x, m["w_up"], dt)
    return h + _mm("sf,fd->sd", u, m["w_down"], dt)


def sequence_loss(cfg: dict, params: dict, tokens, labels,
                  matmul_dtype: Optional[jnp.dtype] = None):
    """Mean next-token cross-entropy of one sequence (tokens, labels: (S,))."""
    h = params["embed"][tokens]

    def body(h, p):
        return jax.checkpoint(lambda h, p: _layer(cfg, p, h, matmul_dtype))(
            h, p), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = _rms(h, params["final_norm"], cfg["norm_eps"])
    logits = _mm("sd,vd->sv", h, params["embed"], matmul_dtype)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - gold)
