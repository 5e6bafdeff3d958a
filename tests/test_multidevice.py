"""Multi-device semantics tests, run in a subprocess so the 8-device placeholder
flag never leaks into the main test session (spec: smoke tests see 1 device)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import compressors as C, distributed as D, ef
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
    dp = 4
    params = {"w": jnp.zeros((8, 4))}
    rng = jax.random.PRNGKey(0)
    grads = jax.random.normal(rng, (dp, 8, 4))
    grads_t = {"w": grads}

    method = ef.EF21SGDM(compressor=C.BlockTopK(block=4, k_per_block=2), eta=0.3)
    # params replicated over 'model' so the per-shard compression domain equals
    # the per-client domain (model-sharded leaves use per-shard Block-TopK,
    # a *different but equally contractive* partition — not bit-identical)
    gspecs = {"w": P("data", None, None)}
    sspecs = {"clients": {k: {"w": P("data", None, None)} for k in ("v", "g")},
              "server": {"w": P(None, None)}}

    for carrier in ("dense", "sparse", "fused", "quant8", "quant4",
                    "fused_quant8", "fused_quant4"):
        efc = D.EFConfig(method=method, carrier=carrier, data_axes=("data",))
        st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
        g_ref, st_ref = D.ef_round(efc, grads_t, st, None)
        with jax.set_mesh(mesh):
            g_sm, st_sm = jax.jit(lambda g, s: D.ef_round_sharded(
                efc, g, s, None, mesh, gspecs, sspecs))(grads_t, st)
        np.testing.assert_allclose(np.asarray(g_sm["w"]),
                                   np.asarray(g_ref["w"]), rtol=1e-5,
                                   atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(st_sm["clients"]["g"]["w"]),
            np.asarray(st_ref["clients"]["g"]["w"]), rtol=1e-5, atol=1e-7)
        print(f"carrier={carrier} OK")

    # dense-quant payload (non-TopK compressor): the shard_map aggregate must
    # dequantize BEFORE the psum, and the Pallas encode_local must match the
    # vmap path's jnp oracle
    m_ht = ef.EF21SGDM(compressor=C.HardThreshold(lam=0.05), eta=0.3)
    for carrier in ("quant8", "quant4"):
        efc = D.EFConfig(method=m_ht, carrier=carrier, data_axes=("data",))
        st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
        g_ref, _ = D.ef_round(efc, grads_t, st, None)
        with jax.set_mesh(mesh):
            g_sm, _ = jax.jit(lambda g, s: D.ef_round_sharded(
                efc, g, s, None, mesh, gspecs, sspecs))(grads_t, st)
        np.testing.assert_allclose(np.asarray(g_sm["w"]),
                                   np.asarray(g_ref["w"]), rtol=1e-5,
                                   atol=1e-7)
        print(f"dense-quant {carrier} OK")

    # wire_is_msg=False on the sharded dense plan: the server must receive the
    # method's MESSAGE (γ·c for Abs), not the raw compressed tensor c
    m_abs = ef.EF21SGDMAbs(compressor=C.HardThreshold(lam=1e-3), eta=0.3,
                           gamma=0.1)
    efc = D.EFConfig(method=m_abs, carrier="dense", data_axes=("data",))
    st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
    g_ref, _ = D.ef_round(efc, grads_t, st, None)
    with jax.set_mesh(mesh):
        g_sm, _ = jax.jit(lambda g, s: D.ef_round_sharded(
            efc, g, s, None, mesh, gspecs, sspecs))(grads_t, st)
    np.testing.assert_allclose(np.asarray(g_sm["w"]), np.asarray(g_ref["w"]),
                               rtol=1e-5)
    print("abs-method message aggregation OK")

    # downlink broadcast on the shard_map path: every device encodes the
    # replicated server innovation (that IS the broadcast — the encoded wire
    # is what travels) and decodes per-client. Proven against the vmap
    # oracle over a sampled (method x uplink x downlink) grid, including
    # both server modes and the fused uplink.
    down_btk = C.BlockTopK(block=4, k_per_block=2)
    grid = [
        ("ef21_sgdm", "dense",  "quant4", down_btk),
        ("ef21_sgdm", "sparse", "quant8", down_btk),
        ("ef21_sgdm", "quant4", "sparse", down_btk),
        ("ef21_sgd",  "fused",  "quant4", down_btk),
        ("ef14_sgd",  "dense",  "sparse", down_btk),
        ("ef21_sgdm", "dense",  "dense",  C.HardThreshold(lam=0.05)),
    ]
    for m_name, up, down, dcomp in grid:
        kwargs = {"compressor": C.BlockTopK(block=4, k_per_block=2)}
        if m_name == "ef21_sgdm":
            kwargs["eta"] = 0.3
        m = ef.make(m_name, **kwargs)
        efc = D.EFConfig(method=m, carrier=up, data_axes=("data",),
                         down_carrier=down, down_compressor=dcomp)
        st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
        assert "h" in st
        g_ref, st_ref = D.ef_round(efc, grads_t, st, None)
        sspecs_d = {"clients": {k: {"w": P("data", None, None)}
                                for k in st["clients"]},
                    "server": {"w": P(None, None)},
                    "h": {"w": P(None, None)}}
        with jax.set_mesh(mesh):
            g_sm, st_sm = jax.jit(lambda g, s: D.ef_round_sharded(
                efc, g, s, None, mesh, gspecs, sspecs_d))(grads_t, st)
        np.testing.assert_allclose(np.asarray(g_sm["w"]),
                                   np.asarray(g_ref["w"]), rtol=1e-5,
                                   atol=1e-7)
        np.testing.assert_allclose(np.asarray(st_sm["h"]["w"]),
                                   np.asarray(st_ref["h"]["w"]), rtol=1e-5,
                                   atol=1e-7)
        # the estimate every device steps with IS its broadcast memory
        np.testing.assert_allclose(np.asarray(g_sm["w"]),
                                   np.asarray(st_sm["h"]["w"]), rtol=0,
                                   atol=0)
        print(f"downlink {m_name}/{up}->{down} OK")
    print("MULTIDEVICE_OK")
""")


def test_shardmap_ef_round_matches_vmap_path():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "MULTIDEVICE_OK" in out.stdout, out.stdout + out.stderr


SCOPES_SCRIPT = textwrap.dedent("""
    import os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import telemetry as T
    from repro.core import compressors as C, distributed as D, ef
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_mesh((4,), ("data",))
    dp = 4
    params = {"w": jnp.zeros((8, 4))}
    grads_t = {"w": jax.random.normal(jax.random.PRNGKey(0), (dp, 8, 4))}
    method = ef.EF21SGDM(compressor=C.BlockTopK(block=4, k_per_block=2),
                         eta=0.3)
    gspecs = {"w": P("data", None, None)}

    def scopes(op):
        return [t for t in re.split(r"[/()]", op) if t in T.SCOPES]

    for carrier, down in (("dense", None), ("fused", None),
                          ("fused_quant8", None), ("quant8", None),
                          ("sparse", "quant8")):
        kw = {} if down is None else dict(
            down_carrier=down, down_compressor=C.BlockTopK(block=4,
                                                           k_per_block=2))
        efc = D.EFConfig(method=method, carrier=carrier, data_axes=("data",),
                         **kw)
        st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
        sspecs = {"clients": {k: {"w": P("data", None, None)}
                              for k in st["clients"]},
                  "server": {"w": P(None, None)}}
        if down is not None:
            sspecs["h"] = {"w": P(None, None)}
        with jax.set_mesh(mesh):
            hlo = jax.jit(lambda g, s: D.ef_round_sharded(
                efc, g, s, None, mesh, gspecs, sspecs)).lower(
                grads_t, st).compile().as_text()
        ops = [(l, m.group(1)) for l in hlo.splitlines()
               for m in [re.search(r'op_name="([^"]*)"', l)] if m]
        seen = {s for _, op in ops for s in scopes(op)}
        want = {T.EF_CLIENT, T.EF_AGGREGATE}
        if down is not None:
            want.add(T.DOWNLINK)
        assert want <= seen, (carrier, seen)
        colls = [(l, op) for l, op in ops
                 if re.search(r" (all-reduce|all-gather)(-start)?\\(", l)]
        assert colls, carrier
        for l, op in colls:
            assert scopes(op) in ([T.EF_AGGREGATE], [T.DOWNLINK]), l
        print(f"scopes {carrier} OK")
    print("SCOPES_OK")
""")


def test_shardmap_round_carries_the_layer_scopes():
    """The shard_map round names its client leg, its aggregation (where
    the collective runs) and its downlink as the vmap round does."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCOPES_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SCOPES_OK" in out.stdout, out.stdout + out.stderr


COLLECTIVE_SCRIPT = textwrap.dedent("""
    import os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import telemetry as T
    from repro.core import compressors as C, distributed as D, ef
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_host4_mesh()
    assert dict(mesh.shape) == {"data": 4, "model": 1}
    assert list(mesh.devices.flat) == jax.devices()[:4]
    dp = 4
    params = {"b": jnp.zeros((6,)), "w": jnp.zeros((8, 4))}
    key = jax.random.PRNGKey(0)
    grads_t = {k: jax.random.normal(jax.random.fold_in(key, i),
                                    (dp,) + v.shape)
               for i, (k, v) in enumerate(sorted(params.items()))}
    method = ef.EF21SGDM(compressor=C.BlockTopK(block=4, k_per_block=2),
                         eta=0.3)
    gspecs = {"b": P("data", None), "w": P("data", None, None)}

    for carrier in ("dense", "fused_quant8", "quant8"):
        efc = D.EFConfig(method=method, carrier=carrier, data_axes=("data",))
        st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
        sspecs = {"clients": {k: gspecs for k in st["clients"]},
                  "server": {"b": P(None), "w": P(None, None)}}
        with jax.set_mesh(mesh):
            hlo = jax.jit(lambda g, s: D.ef_round_sharded(
                efc, g, s, None, mesh, gspecs, sspecs)).lower(
                grads_t, st).compile().as_text()
        colls = [(l, re.search(r'op_name="([^"]*)"', l)) for l in
                 hlo.splitlines()
                 if re.search(r" (all-reduce|all-gather)(-start)?\\(", l)]
        assert colls, carrier
        for l, m in colls:
            path = re.split(r"[/()]", m.group(1)) if m else []
            assert T.EF_COLLECTIVE in path, l
            assert T.EF_COLLECTIVE not in T.SCOPES
            assert (path.index(T.EF_AGGREGATE)
                    < path.index(T.EF_COLLECTIVE)), l
        rows = T.collectives(last_round=True)
        ops = {"dense": {"all-reduce"}, "fused_quant8": {"all-reduce"},
               "quant8": {"all-gather"}}[carrier]
        assert rows and {r["op"] for r in rows} == ops, (carrier, rows)
        assert all(r["axes"] == ("data",) for r in rows), rows
        print(f"collective scope {carrier} OK")

    # fused_quant8: one f32 all-reduce per leaf, its local elements' bytes,
    # recorded once in each trace of the round
    efc = D.EFConfig(method=method, carrier="fused_quant8",
                     data_axes=("data",))
    st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
    sspecs = {"clients": {k: gspecs for k in st["clients"]},
              "server": {"b": P(None), "w": P(None, None)}}
    before = len(T.collectives())
    for _ in range(2):
        with jax.set_mesh(mesh):
            jax.jit(lambda g, s: D.ef_round_sharded(
                efc, g, s, None, mesh, gspecs, sspecs)).lower(grads_t, st)
    rows = T.collectives()[before:]
    assert len({r["round"] for r in rows}) == 2, rows
    last = T.collectives(last_round=True)
    assert [r["bytes"] for r in last] == [4 * 6, 4 * 32], last
    assert [r["elements"] for r in last] == [6, 32], last
    assert len(rows) == 2 * len(last), rows
    print("COLLECTIVES_OK")
""")


def test_sharded_round_scopes_and_counts_its_collectives():
    """Every all-reduce and all-gather of the sharded EF aggregation runs
    under ``ef_collective`` inside ``ef_aggregate``, on mesh host4 (the
    first four devices), and the collective counter records each leaf's
    f32 operand once per trace of the round."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", COLLECTIVE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "COLLECTIVES_OK" in out.stdout, out.stdout + out.stderr
