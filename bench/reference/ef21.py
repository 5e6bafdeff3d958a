"""EF21-SGDM (Algorithm 1 of Fatkhullin et al., 2023) for n clients in
plain float32, with the cell's wire: Block-TopK within blocks of the
flattened leaf, then a per-block absmax int8 code whose decode is what
both the client and the server integrate.

    v_i <- v_i + eta (grad_i - v_i)
    c_i <- decode(encode(BlockTopK(v_i - g_i)))
    g_i <- g_i + c_i ;  g <- g + mean_i c_i ;  x <- x - lr g

At step 0, v_i = g_i = grad_i(x0, batch 0) and g = mean_i g_i. Clients sit
on a one-axis mesh over the cell's chips, each chip holding its share; the
mean over clients is the one collective.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.reference import model as ref_model


def _block_code(delta, block: int, ratio: float, bits: int):
    """The decoded wire of one client's leaf."""
    d = delta.size
    b = min(block, d)
    kb = max(1, min(b, int(round(ratio * b))))
    nb = -(-d // b)
    x = jnp.pad(delta.reshape(-1), (0, nb * b - d)).reshape(nb, b)
    ab = jnp.abs(x)
    thr = jax.lax.top_k(ab, kb)[0][:, -1:]
    c = jnp.where(ab >= thr, x, 0.0)
    qmax = 2.0 ** (bits - 1) - 1
    scale = jnp.max(jnp.abs(c), axis=1, keepdims=True) / qmax
    q = jnp.clip(jnp.round(c / jnp.where(scale > 0, scale, 1.0)), -qmax,
                 qmax)
    return (q * scale).reshape(-1)[:d].reshape(delta.shape)


def round_blocks(v_new, g, server, kb: int, bits: int):
    """Lines 7-8 and the server's mean on whole selection blocks, in numpy
    float32, from a round's own inputs: the clients' v' and g as
    (clients, blocks, block), the server estimate as (blocks, block).
    Returns (g', server', each client's decoded message, their mean)."""
    f32 = np.float32
    delta = v_new.astype(f32) - g.astype(f32)
    ab = np.abs(delta)
    thr = -np.partition(-ab, kb - 1, axis=-1)[..., kb - 1:kb]
    c = np.where(ab >= thr, delta, f32(0))
    qmax = f32(2 ** (bits - 1) - 1)
    scale = np.max(np.abs(c), axis=-1, keepdims=True) / qmax
    q = np.clip(np.round(c / np.where(scale > 0, scale, f32(1))), -qmax,
                qmax)
    c_hat = q * scale
    mean = c_hat.sum(axis=0) / f32(c_hat.shape[0])
    return g + c_hat, server + mean, c_hat, mean


class Reference:
    """Jitted reference programs for one cell on ``devices``."""

    def __init__(self, arch: dict, ef: dict, clients: int, devices,
                 matmul_dtype: Optional[jnp.dtype] = None):
        self.arch, self.ef, self.n = arch, ef, clients
        self.mesh = jax.sharding.Mesh(devices, ("c",))
        self.dt = matmul_dtype
        if clients % len(devices):
            raise ValueError(f"{clients} clients over {len(devices)} chips")
        rep = NamedSharding(self.mesh, P())
        cl = NamedSharding(self.mesh, P("c"))
        self.rep, self.cl = rep, cl
        smap = partial(jax.shard_map, mesh=self.mesh, check_vma=False)
        self.init = jax.jit(smap(self._init, in_specs=(P(), P("c"), P("c")),
                                 out_specs=(P("c"), P("c"), P(), P())))
        self.step = jax.jit(
            smap(self._step, in_specs=(P(), P("c"), P("c"), P(), P("c"),
                                       P("c")),
                 out_specs=(P(), P("c"), P("c"), P(), P())),
            donate_argnums=(0, 1, 2, 3))
        self.params = jax.jit(lambda key: ref_model.init(arch, key),
                              out_shardings=rep)

    # -- pieces run on each chip (shard_map bodies) -------------------------
    def _client_grad(self, params, toks, labs):
        """Mean loss and gradient over one client's rows, a row at a time."""
        vg = jax.value_and_grad(
            lambda p, t, y: ref_model.sequence_loss(self.arch, p, t, y,
                                                    self.dt))

        def row(acc, xs):
            loss, g = vg(params, *xs)
            return (acc[0] + loss,
                    jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros(()), jax.tree_util.tree_map(jnp.zeros_like, params))
        (loss, g), _ = jax.lax.scan(row, zero, (toks, labs))
        rows = toks.shape[0]
        return loss / rows, jax.tree_util.tree_map(lambda x: x / rows, g)

    def _grads(self, params, toks, labs):
        return jax.lax.map(lambda tl: self._client_grad(params, *tl),
                           (toks, labs))

    def _mean(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x.sum(0), "c") / self.n, tree)

    def _init(self, params, toks, labs):
        loss, g0 = self._grads(params, toks, labs)
        return g0, g0, self._mean(g0), self._mean(loss)

    def _step(self, params, v, g, server, toks, labs):
        ef = self.ef
        loss, grads = self._grads(params, toks, labs)
        v = jax.tree_util.tree_map(lambda v, x: v + ef["eta"] * (x - v),
                                   v, grads)
        code = jax.vmap(partial(_block_code, block=ef["block"],
                                ratio=ef["ratio"], bits=ef["bits"]))
        c = jax.tree_util.tree_map(lambda v, g: code(v - g), v, g)
        g = jax.tree_util.tree_map(jnp.add, g, c)
        server = jax.tree_util.tree_map(jnp.add, server, self._mean(c))
        params = jax.tree_util.tree_map(lambda p, s: p - ef["lr"] * s,
                                        params, server)
        return params, v, g, server, self._mean(loss)

    # -- driver ---------------------------------------------------------------
    def run(self, seed: int, batches, steps: int = 3):
        """Follow ``steps`` steps from the seed. ``batches(t)`` gives step
        t's (tokens, labels) as (clients, rows, S) arrays. Returns the
        losses of the steps, the server estimate after the first step,
        and the parameters before the first step and after the last one,
        all on the chips."""
        put = lambda x: jax.device_put(x, self.cl)  # noqa: E731
        params = self.params(jax.random.PRNGKey(seed))
        p0 = jax.tree_util.tree_map(jnp.copy, params)
        t0, y0 = batches(0)
        v, g, server, _ = self.init(params, put(t0), put(y0))
        losses, s1 = [], None
        for t in range(steps):
            toks, labs = batches(t)
            params, v, g, server, loss = self.step(params, v, g, server,
                                                   put(toks), put(labs))
            losses.append(loss)
            if t == 0:
                s1 = jax.tree_util.tree_map(jnp.copy, server)
        return {"losses": losses, "s1": s1, "s_last": server, "p0": p0,
                "p_last": params}
