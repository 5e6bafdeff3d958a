"""Blocked causal flash attention (Pallas TPU).

Canonical online-softmax formulation with a (B, H, nq, nk) grid; the kv-block
index is the innermost (sequential) grid dimension, accumulating into VMEM
scratch (running max m, running sum l, output accumulator acc) and finalizing on
the last kv block. Strictly-future kv blocks are skipped with @pl.when — on real
TPU this halves causal FLOPs vs. the masked pure-JAX path (models/layers.py),
which is exactly the §Perf "banded/blocked schedule" optimization.

Block sizes are MXU-aligned (multiples of 128 on the lane dim; head_dim padded by
the wrapper in ops.py when needed). VMEM working set per program:
  q(bq·hd) + k(bk·hd) + v(bk·hd) + acc(bq·hd) + scores(bq·bk) + m,l(2·bq)
e.g. bq=bk=256, hd=128, f32 ≈ 0.75 MiB ≪ 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, nk: int, causal: bool, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (qi * bq + bq - 1 >= ki * bk) if causal else True

    @pl.when(run if causal else ki >= 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False) -> jax.Array:
    """q,k,v: (B, S, H, hd) with equal head counts (wrapper expands GQA)."""
    B, S, H, hd = q.shape
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = hd ** -0.5

    # layout: (B, H, S, hd) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk,
                               causal=causal, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
