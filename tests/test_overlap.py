"""Comm/compute overlap (DESIGN.md §10): the double-buffered ppermute ring in
``ef_round_sharded`` is BIT-identical to the blocking all-gather anchor —
overlap may only move the collective in time, never change a single bit — and
the overlap flag survives a kill-and-resume. The multi-device parts run in a
subprocess so the 8-device placeholder flag never leaks into the main test
session (same idiom as tests/test_multidevice.py)."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import carriers as carrier_lib
    from repro.core import compressors as C, distributed as D, ef
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
    dp = 4

    # --- ring_all_gather == lax.all_gather (the bit-identity that makes the
    # overlapped transport an anchor-preserving rewrite). check_vma=False:
    # ppermute-based gathers defeat shard_map's static replication inference.
    x = jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6)

    def plain(xs):
        ring = carrier_lib.ring_all_gather(xs, "data")
        ref = jax.lax.all_gather(xs, "data")
        return ring, ref

    sm = jax.shard_map(plain, mesh=mesh, in_specs=P("data", None),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    ring, ref = sm(x)
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))

    def with_fn(xs):                      # per-chunk decode hook
        ring = carrier_lib.ring_all_gather(xs, "data", fn=lambda c: c * 2.0)
        ref = jax.lax.all_gather(xs * 2.0, "data")
        return ring, ref

    sm = jax.shard_map(with_fn, mesh=mesh, in_specs=P("data", None),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    ring, ref = sm(x)
    np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))
    print("ring_all_gather OK")

    # --- overlap on/off bit-identity through the production jitted
    # ef_round_sharded, over a (method x carrier) sample grid: message,
    # every client state leaf, and the server estimate must be EQUAL —
    # not close.
    params = {"w": jnp.zeros((8, 4))}
    rng = jax.random.PRNGKey(0)
    grads_t = {"w": jax.random.normal(rng, (dp, 8, 4))}
    gspecs = {"w": P("data", None, None)}
    btk = C.BlockTopK(block=4, k_per_block=2)

    grid = [("ef21_sgdm", c) for c in carrier_lib.REGISTRY] + [
        ("ef21_sgd", "sparse"), ("ef21_sgd", "quant8"),
        ("ef21_sgd", "fused_quant8")]
    for m_name, carrier in grid:
        kwargs = {"compressor": btk}
        if m_name == "ef21_sgdm":
            kwargs["eta"] = 0.3
        method = ef.make(m_name, **kwargs)
        st0 = None
        outs = {}
        for overlap in (False, True):
            efc = D.EFConfig(method=method, carrier=carrier,
                             data_axes=("data",), overlap=overlap)
            st = D.init_ef_state(efc, params, dp, init_grads=grads_t)
            sspecs = {"clients": {k: {"w": P("data", None, None)}
                                  for k in st["clients"]},
                      "server": {"w": P(None, None)}}
            with jax.set_mesh(mesh):
                outs[overlap] = jax.jit(
                    functools.partial(D.ef_round_sharded, efc, mesh=mesh,
                                      grads_specs=gspecs,
                                      state_specs=sspecs))(
                    grads_t, st, None)
        (g_off, st_off), (g_on, st_on) = outs[False], outs[True]
        np.testing.assert_array_equal(np.asarray(g_off["w"]),
                                      np.asarray(g_on["w"]))
        for key in st_off["clients"]:
            np.testing.assert_array_equal(
                np.asarray(st_off["clients"][key]["w"]),
                np.asarray(st_on["clients"][key]["w"]))
        np.testing.assert_array_equal(np.asarray(st_off["server"]["w"]),
                                      np.asarray(st_on["server"]["w"]))
        print(f"overlap bit-identity {m_name}/{carrier} OK")
    print("OVERLAP_OK")
""")


def test_overlap_is_bit_identical_to_blocking_anchor():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "OVERLAP_OK" in out.stdout, out.stdout + out.stderr


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def test_kill_and_resume_under_overlap(tmp_path):
    """The overlap flag rides the spec hash through checkpointing: a killed
    overlap run resumes with overlap still on and the trajectory is
    bit-identical to the uninterrupted overlap run."""
    from repro.launch.session import Session
    from repro.launch.spec import RunSpec

    base = RunSpec(arch="smollm-360m", smoke=True, clients=2, global_batch=4,
                   seq_len=32, overlap=True)
    unint = Session(base)
    unint.train(4, log_every=1)

    interrupted = Session(dataclasses.replace(base, ckpt_dir=str(tmp_path)))
    interrupted.train(2, log_every=1)
    del interrupted                        # "kill" the process

    resumed = Session.resume(str(tmp_path))
    assert resumed.step == 2
    assert resumed.spec.overlap is True
    assert resumed.spec.spec_hash() == base.spec_hash()
    resumed.train(4, log_every=1)
    assert _leaves_equal(unint.params, resumed.params)
    assert _leaves_equal(unint.ef_state, resumed.ef_state)
