"""Causal GQA attention's two training paths: the fused flash kernel
(kernels/ops.py::causal_gqa_attention, in the Pallas interpreter here) and
chunked jnp attention (models/layers.py::chunked_attention) compute the same
function and gradients, and ``attn_apply`` picks the kernel from the call's
shape alone, as ``repro.telemetry.attention_calls`` records."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import base as cb
from repro.kernels import ops
from repro.models import layers as L
from repro.models import model as M

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _qkv(B, S, H, KV, hd, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(S + H + hd), 3)
    return [jax.random.normal(k, (B, S, n, hd), dtype)
            for k, n in zip(ks, (H, KV, KV))]


def _attention_case(H, KV, hd, S, window):
    """Forward output and q, k, v gradients, kernel against chunked (f32)."""
    q, k, v = _qkv(1, S, H, KV, hd)
    w = jnp.cos(jnp.arange(hd, dtype=jnp.float32))     # a non-uniform cotangent

    def kernel(q, k, v):
        return ops.causal_gqa_attention(q * hd ** -0.5, k, v)

    def chunked(q, k, v):
        return L.chunked_attention(q, k, v, chunk=128, window=window)

    outs, grads = [], []
    for f in (kernel, chunked):
        outs.append(f(q, k, v))
        grads.append(jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(
            q, k, v))
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=1e-5, atol=1e-5)
    for name, a, b in zip("qkv", *grads):
        assert _rel(a, b) < 1e-5, (name, _rel(a, b))


def _model_case():
    """A tiny dense config's loss and parameter gradients at S 256, with
    every layer rematerialised as the cells train, through both paths: in
    f32 they agree to rounding; in the configured bf16 the kernel's are no
    farther from the f32 ones than the chunked path's."""
    bf16 = dataclasses.replace(cb.get_smoke("smollm-360m"), remat=True)
    f32 = dataclasses.replace(bf16, dtype="float32")
    rng = jax.random.PRNGKey(3)
    params = M.init_params(bf16, rng)
    tokens = jax.random.randint(rng, (2, 256), 0, bf16.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def loss_grad(cfg, path):
        block = ops.causal_attention_block
        if path == "chunked":
            ops.causal_attention_block = lambda S: None
        try:
            telemetry.clear_attention_calls()
            out = jax.jit(jax.value_and_grad(
                lambda p: M.train_loss(cfg, p, batch)[0]))(params)
            assert {a["path"] for a in telemetry.attention_calls()} == {path}
        finally:
            ops.causal_attention_block = block
        return float(out[0]), out[1]

    def worst(g, ref):
        return max(_rel(a, _get(ref, path)) for path, a in
                   jax.tree_util.tree_leaves_with_path(g))

    lk, gk = loss_grad(f32, "kernel")
    lc, gc = loss_grad(f32, "chunked")
    assert abs(lk - lc) <= 1e-6 * abs(lc), (lk, lc)
    assert worst(gk, gc) < 1e-5, worst(gk, gc)
    bk, gbk = loss_grad(bf16, "kernel")
    bc, gbc = loss_grad(bf16, "chunked")
    # bf16 operands put both paths about 1.5e-2 off the f32 gradients; the
    # kernel's rounding (f32 p before PV, bf16 dS) must not add to that
    assert abs(bk - lc) <= 1.25 * abs(bc - lc), (bk, bc, lc)
    assert worst(gbk, gc) <= 1.1 * worst(gbc, gc), \
        (worst(gbk, gc), worst(gbc, gc))


def _get(tree, path):
    for key in path:
        tree = tree[getattr(key, "key", getattr(key, "idx", None))]
    return tree


@pytest.mark.parametrize("case", [
    ("attention", 15, 5, 64, 256, None),
    ("attention", 15, 5, 64, 512, None),
    ("attention", 4, 1, 120, 256, None),
    ("attention", 4, 1, 120, 512, None),
    ("attention", 4, 1, 120, 512, 4096),   # danube3's window binds nothing
    ("model",),
], ids=lambda c: "-".join(str(x) for x in c))
def test_kernel_path_matches_chunked(case):
    if case[0] == "model":
        _model_case()
    else:
        _attention_case(*case[1:])


D_CELL = {"smollm": dict(d=960, H=15, KV=5, hd=64, B=4, S=1024, window=None),
          "danube3": dict(d=3840, H=32, KV=8, hd=120, B=4, S=2048,
                          window=4096)}


def _trace(*, d=64, H=4, KV=2, hd=16, B=1, S=64, window=None, cap=None,
           cache_len=None, decode=False):
    """Trace one ``attn_apply`` (shapes only, nothing runs) and return the
    attention calls it records."""
    p = jax.eval_shape(lambda: L.attn_init(jax.random.PRNGKey(0), d, H, KV,
                                           hd, jnp.bfloat16))
    n = 1 if decode else S
    x = jax.ShapeDtypeStruct((B, n, d), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((B, n), jnp.int32)
    cache = None
    if cache_len is not None:
        c = jax.ShapeDtypeStruct((B, cache_len, KV, hd), jnp.bfloat16)
        cache = (c, c)

    def f(p, x, pos, cache):
        return L.attn_apply(p, x, pos, rope_theta=1e4, eps=1e-6, chunk=512,
                            window=window, cap=cap, cache=cache,
                            pos_scalar=jnp.asarray(S - 1) if decode else None)

    telemetry.clear_attention_calls()
    assert telemetry.attention_calls() == []
    jax.eval_shape(f, p, x, pos, cache)
    return telemetry.attention_calls()


@pytest.mark.parametrize("call,path", [
    (dict(S=64), "chunked"),                          # under one block
    (dict(S=256, window=128), "chunked"),             # a binding window
    (dict(S=256, cap=50.0), "chunked"),               # gemma2's softcap
    (dict(S=256, cache_len=256), "chunked"),          # prefill into a cache
    (dict(S=256, cache_len=256, decode=True), "decode"),
    (dict(S=256), "kernel"),
    (dict(S=256, window=256), "kernel"),              # a window that binds nothing
    (D_CELL["smollm"], "kernel"),
    (D_CELL["danube3"], "kernel"),
], ids=["S64", "window", "softcap", "prefill", "decode", "S256",
        "wide_window", "smollm_cell", "danube3_cell"])
def test_attention_dispatch_follows_the_shape(call, path):
    calls = _trace(**call)
    assert len(calls) == 1, calls
    rec = calls[0]
    assert rec["path"] == path
    hd = call.get("hd", 16)
    assert (rec["B"], rec["S"], rec["H"], rec["KV"], rec["hd"]) == (
        call.get("B", 1), 1 if call.get("decode") else call["S"],
        call.get("H", 4), call.get("KV", 2), hd)
    if path == "kernel":
        assert rec["block"] == ops.causal_attention_block(call["S"])
        assert call["S"] % rec["block"] == 0
    else:
        assert rec["block"] is None


def test_attention_runs_under_its_scope():
    """Both paths lower under the ``attention`` scope, so a trace can split
    attention out of the model's time."""
    for call in (dict(S=256), dict(S=64)):
        cfg = dict(d=64, H=4, KV=2, hd=16)
        p = L.attn_init(jax.random.PRNGKey(0), cfg["d"], cfg["H"], cfg["KV"],
                        cfg["hd"], jnp.float32)
        x = jnp.ones((1, call["S"], cfg["d"]), jnp.float32)
        pos = jnp.arange(call["S"])[None]
        hlo = jax.jit(lambda p, x: L.attn_apply(
            p, x, pos, rope_theta=1e4, eps=1e-6, chunk=512)[0]).lower(
                p, x).as_text(debug_info=True)
        assert f"/{telemetry.ATTENTION}/" in hlo, call


MESH_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import telemetry
    from repro.core import distributed as dist
    from repro.launch import mesh as mesh_lib
    from repro.models import layers as L

    data, H, KV = map(int, sys.argv[1:])
    S, hd, d = 256, 64, 64
    mesh = mesh_lib.make_mesh((data, 4 // data), ("data", "model"))
    params = L.attn_init(jax.random.PRNGKey(0), d, H, KV, hd, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (data, S, d))

    def loss(p, x):
        pos = jnp.broadcast_to(jnp.arange(S), x.shape[:2])
        out, _ = L.attn_apply(p, x, pos, rope_theta=1e4, eps=1e-6, chunk=128)
        return jnp.mean(out ** 2), ()

    def step(p, x):
        return dist.per_client_value_and_grad(loss, p, x, data)

    want = jax.jit(step)(params, x)
    with jax.set_mesh(mesh):
        telemetry.clear_attention_calls()
        got = jax.jit(step)(
            jax.device_put(params, NamedSharding(mesh, P())),
            jax.device_put(x, NamedSharding(mesh, P("data"))))
        assert {a["path"] for a in telemetry.attention_calls()} == {"kernel"}
    leaf = jax.tree_util.tree_leaves(got[2])[0]
    assert leaf.sharding.spec[0] == "data", leaf.sharding
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-7)
    print("MESH_OK")
""")


@pytest.mark.parametrize("data,H,KV", [(4, 4, 2), (2, 4, 2), (2, 3, 1)],
                         ids=["data4", "heads_over_model", "heads_whole"])
def test_kernel_path_under_a_mesh_matches_one_device(data, H, KV):
    """Under a data × model mesh of four devices the kernel runs on each
    device's shard (clients over ``data``; heads over ``model`` where they
    divide, else whole) and gives one device's loss and gradients."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT, str(data),
                          str(H), str(KV)], env=env, capture_output=True,
                         text=True, timeout=600)
    assert "MESH_OK" in out.stdout, out.stdout + out.stderr
