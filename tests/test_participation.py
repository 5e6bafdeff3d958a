"""Partial participation anchor grid (core/participation.py, DESIGN.md §11).

The load-bearing acceptance criterion of the participation PR: a SAMPLED
cohort at fraction=1.0 is BIT-identical to today's full-participation
synchronous path — params, the complete ef_state (gᵢ, momentum, and the
downlink memory h), and per-direction wire accounting — on all three
runtimes (the production vmap train step / ef_round, the shard_map
ef_round_sharded, and the vmap simulator), across a
(method × carrier × downlink) sample including per-group schedules. Plus:
fractional cohorts actually freeze non-sampled clients' whole EF state, the
construction errors hold, and kill-and-resume replays the identical cohort
sequence mid-stream (the seeded mask is pure in (seed, step)).
"""
import dataclasses
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.core import compressors as C
from repro.core import distributed as D
from repro.core import ef, problems, simulate
from repro.core import participation as part_lib
from repro.core import schedule as S
from repro.launch import build as build_lib
from repro.launch import mesh as mesh_lib
from repro.launch import session as session_lib
from repro.launch.session import Session
from repro.launch.spec import RunSpec

BTK = C.BlockTopK(block=8, k_per_block=3)
DOWN_BTK = C.BlockTopK(block=8, k_per_block=2)
TINY = dict(arch="smollm-360m", smoke=True, clients=2, global_batch=4,
            seq_len=32)
FULL_1 = part_lib.Participation(mode="sampled", fraction=1.0)
HALF = part_lib.Participation(mode="sampled", fraction=0.5, seed=3)


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _loss_fn(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


@pytest.fixture
def lin_setup():
    rng = jax.random.PRNGKey(0)
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    x = jax.random.normal(rng, (16, 8))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (8, 4))
    return params, {"x": x, "y": x @ w}


def _run_train(setup, efc, steps=6, dp=4):
    from repro.optim import optimizer as opt_lib
    params, batch = setup
    opt = opt_lib.sgd(0.2)
    step = jax.jit(D.make_train_step(_loss_fn, efc, opt, dp))
    _, _, g0 = D.per_client_value_and_grad(_loss_fn, params, batch, dp)
    p, os_, es = params, opt.init(params), D.init_ef_state(
        efc, params, dp, init_grads=g0)
    rng = jax.random.PRNGKey(1)
    for t in range(steps):
        p, os_, es, _ = step(p, os_, es, batch, jax.random.fold_in(rng, t), t)
    return p, es


def _grid_cells():
    for m_name in ("ef21_sgdm", "ef21_sgd", "ef14_sgd"):
        for carrier in ("dense", "sparse", "quant4", "fused"):
            if carrier == "fused" and m_name == "ef14_sgd":
                continue                      # fused covers EF21-SGD(M) only
            for down in ("dense", "quant4"):
                yield m_name, carrier, down


def _make_method(m_name):
    kwargs = {"compressor": BTK}
    if m_name == "ef21_sgdm":
        kwargs["eta"] = 0.3
    return ef.make(m_name, **kwargs)


# ---------------------------------------------------------------------------
# anchor runtime 1: the production vmap train step (ef_round)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m_name,carrier,down", list(_grid_cells()))
def test_sampled_fraction_one_bit_matches_full_ef_round(lin_setup, m_name,
                                                        carrier, down):
    """mode=full and mode=sampled fraction=1.0 are BIT-identical — params
    and the full ef_state (clients, server, downlink h) after a multi-step
    production train run — for every (method × carrier × downlink) cell."""
    method = _make_method(m_name)
    down_comp = DOWN_BTK if down != "dense" else None
    full = D.EFConfig(method=method, carrier=carrier, down_carrier=down,
                      down_compressor=down_comp)
    sampled = dataclasses.replace(full, participation=FULL_1)
    p0, es0 = _run_train(lin_setup, full)
    p1, es1 = _run_train(lin_setup, sampled)
    assert sorted(es0) == sorted(es1)          # same state tree (incl. h)
    assert _leaves_equal(p0, p1)
    assert _leaves_equal(es0, es1)


def test_sampled_fraction_one_bit_matches_full_under_schedule(lin_setup):
    """The anchor composes with per-group schedules (PR 5): a mixed
    schedule's masked path at fraction=1.0 is bit-identical too."""
    method = ef.make("ef21_sgdm", compressor=BTK, eta=0.3)
    sched = S.CompressionSchedule((
        S.Group(pattern="b", carrier="dense"),
        S.Group(pattern="*", compressor=BTK, carrier="sparse",
                down_carrier="quant4", down_compressor=DOWN_BTK),
    ))
    full = D.EFConfig(method=method, schedule=sched)
    sampled = dataclasses.replace(full, participation=FULL_1)
    p0, es0 = _run_train(lin_setup, full)
    p1, es1 = _run_train(lin_setup, sampled)
    assert _leaves_equal(p0, p1) and _leaves_equal(es0, es1)


def test_sampled_cohort_freezes_non_sampled_state_ef_round(lin_setup):
    """The Bells & Whistles frozen-client invariant on the production step:
    a fraction=0.5 round leaves every non-sampled client's ENTIRE state
    tree (gᵢ AND momentum) bit-untouched, while sampled clients move."""
    method = ef.make("ef21_sgdm", compressor=BTK, eta=0.3)
    efc = D.EFConfig(method=method, carrier="sparse", participation=HALF)
    params, batch = lin_setup
    dp = 4
    _, _, g0 = D.per_client_value_and_grad(_loss_fn, params, batch, dp)
    # feed grads ≠ gᵢ so sampled clients have a nonzero delta to compress
    grads = jax.tree_util.tree_map(lambda g: 2.0 * g + 1.0, g0)
    es = D.init_ef_state(efc, params, dp, init_grads=g0)
    for t in range(3):
        mask = part_lib.cohort_mask_np(HALF, dp, t)
        assert mask.sum() == HALF.cohort_size(dp)
        _, es_new = D.ef_round(efc, grads, es, None, step=jnp.int32(t))
        moved = 0
        for k in es["clients"]:
            for new_l, old_l in zip(
                    jax.tree_util.tree_leaves(es_new["clients"][k]),
                    jax.tree_util.tree_leaves(es["clients"][k])):
                for i in range(dp):
                    same = np.array_equal(np.asarray(new_l)[i],
                                          np.asarray(old_l)[i])
                    if mask[i] == 0.0:
                        assert same, f"non-sampled client {i} state moved"
                    elif not same:
                        moved += 1
        assert moved > 0, "sampled clients never moved"
        es = es_new


def test_sampled_requires_step_and_async_refuses_sync_runtimes(lin_setup):
    method = ef.make("ef21_sgdm", compressor=BTK, eta=0.3)
    params, batch = lin_setup
    _, _, grads = D.per_client_value_and_grad(_loss_fn, params, batch, 4)
    efc = D.EFConfig(method=method, carrier="sparse", participation=HALF)
    es = D.init_ef_state(efc, params, 4, init_grads=grads)
    with pytest.raises(ValueError, match="pass step="):
        D.ef_round(efc, grads, es, None)
    efc_async = D.EFConfig(
        method=method, carrier="sparse",
        participation=part_lib.Participation(mode="async"))
    with pytest.raises(ValueError, match="run_async"):
        D.ef_round(efc_async, grads, es, None, step=jnp.int32(0))
    with pytest.raises(ValueError, match="run_async"):
        simulate.run(problems.QuadraticT1(), method,
                     simulate.SimConfig(
                         n=4, steps=2,
                         participation=part_lib.Participation(mode="async")),
                     jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# anchor runtime 2: the vmap simulator (wire accounting included)
# ---------------------------------------------------------------------------

def test_sampled_fraction_one_bit_matches_full_simulator():
    """Same anchor on the simulator: whole trajectory AND every wire
    accounting key (per-direction words, coords) bit-equal at fraction=1.0;
    a fractional cohort reports fraction·n uplink wires honestly while the
    downlink broadcast still reaches all n links."""
    prob = problems.MLPClassification(n=4, m_per_client=64)
    btk = C.BlockTopK(block=64, k_per_block=8)
    method = ef.EF21SGDM(compressor=btk, eta=0.2)
    down = C.BlockTopK(block=64, k_per_block=4)
    for carrier in ("dense", "sparse", "quant4"):
        base = simulate.SimConfig(n=4, steps=5, gamma=0.05, carrier=carrier,
                                  down_carrier="quant4",
                                  down_compressor=down)
        full = simulate.run_numpy(prob, method, base, seed=0)
        frac1 = simulate.run_numpy(
            prob, method,
            dataclasses.replace(base, participation=FULL_1), seed=0)
        assert sorted(full) == sorted(frac1)
        for k in full:
            assert _leaves_equal(full[k], frac1[k]), (carrier, k)
        half = simulate.run_numpy(
            prob, method,
            dataclasses.replace(base, participation=HALF), seed=0)
        # uplink scales to the cohort (m = 2 of n = 4); downlink stays × n
        assert half["wire_words_up_per_round"] \
            == full["wire_words_up_per_round"] / 2
        assert half["coords_per_round"] == full["coords_per_round"] / 2
        assert half["wire_words_down_per_round"] \
            == full["wire_words_down_per_round"]


def test_sampled_simulator_group_accounting_scales_per_group():
    prob = problems.MLPClassification(n=4, m_per_client=64)
    method = ef.EF21SGDM(compressor=C.BlockTopK(block=64, k_per_block=8),
                         eta=0.2)
    sched = S.CompressionSchedule((
        S.Group(pattern="b", carrier="dense"),
        S.Group(pattern="*", compressor=C.BlockTopK(block=64, k_per_block=8),
                carrier="sparse"),
    ))
    base = simulate.SimConfig(n=4, steps=3, gamma=0.05, schedule=sched)
    full = simulate.run_numpy(prob, method, base, seed=0)
    half = simulate.run_numpy(
        prob, method, dataclasses.replace(base, participation=HALF), seed=0)
    assert tuple(half["wire_words_up_per_group"]) == tuple(
        w / 2 for w in full["wire_words_up_per_group"])
    assert tuple(half["wire_words_down_per_group"]) == tuple(
        full["wire_words_down_per_group"])


# ---------------------------------------------------------------------------
# anchor runtime 3: ef_round_sharded (shard_map, 8 forced host devices)
# ---------------------------------------------------------------------------

def _sharded_setup(efc):
    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
    rng = jax.random.PRNGKey(0)
    params = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))}
    grads = {"w": jax.random.normal(rng, (4, 4, 8)),
             "b": jax.random.normal(jax.random.fold_in(rng, 1), (4, 8))}
    st = D.init_ef_state(efc, params, 4, init_grads=grads)
    gspecs = {"w": P("data", None, None), "b": P("data", None)}
    cl = {"w": P("data", None, None), "b": P("data", None)}
    sv = {"w": P(None, None), "b": P(None)}
    sspecs = {"clients": {k: cl for k in st["clients"]}, "server": sv}
    if "h" in st:
        sspecs["h"] = sv
    return mesh, grads, st, gspecs, sspecs


@pytest.mark.parametrize("carrier", ["dense", "sparse", "quant4", "fused"])
def test_sampled_fraction_one_bit_matches_full_sharded(carrier):
    method = ef.make("ef21_sgdm", compressor=BTK, eta=0.3)
    full = D.EFConfig(method=method, carrier=carrier, data_axes=("data",),
                      down_carrier="quant4", down_compressor=DOWN_BTK)
    sampled = dataclasses.replace(full, participation=FULL_1)
    mesh, grads, st, gspecs, sspecs = _sharded_setup(full)
    with jax.set_mesh(mesh):
        g0, s0 = jax.jit(lambda g, s: D.ef_round_sharded(
            full, g, s, None, mesh, gspecs, sspecs))(grads, st)
        g1, s1 = jax.jit(lambda g, s, t: D.ef_round_sharded(
            sampled, g, s, None, mesh, gspecs, sspecs, step=t))(
            grads, st, jnp.int32(0))
    assert _leaves_equal(g0, g1) and _leaves_equal(s0, s1)


def test_sharded_sampled_cohort_matches_vmap_sampled():
    """The masked shard_map path computes the SAME sampled round as the
    masked vmap path (same (seed, step) → same cohort on both runtimes)."""
    method = ef.make("ef21_sgdm", compressor=BTK, eta=0.3)
    efc = D.EFConfig(method=method, carrier="sparse", data_axes=("data",),
                     participation=HALF)
    mesh, grads, st, gspecs, sspecs = _sharded_setup(efc)
    with jax.set_mesh(mesh):
        g_sh, s_sh = jax.jit(lambda g, s, t: D.ef_round_sharded(
            efc, g, s, None, mesh, gspecs, sspecs, step=t))(
            grads, st, jnp.int32(1))
    g_vm, s_vm = D.ef_round(efc, grads, st, None, step=jnp.int32(1))
    for a, b in zip(jax.tree_util.tree_leaves((g_vm, s_vm)),
                    jax.tree_util.tree_leaves((g_sh, s_sh))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# construction errors: the fused wire has no per-client wire to mask
# ---------------------------------------------------------------------------

def test_sampled_fused_quant_is_a_construction_error():
    with pytest.raises(ValueError, match="no per-client wire"):
        RunSpec(**TINY, carrier="fused_quant8",
                compressor_kw={"block": 8, "k_per_block": 3},
                participation={"mode": "sampled", "fraction": 0.5})
    # the authoritative build-layer check catches hand-built configs too
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    plan = None
    from repro.launch import shardings as sh
    plan = sh.ShardPlan()
    with pytest.raises(ValueError, match="no per-client wire"):
        build_lib.default_ef_config(
            mesh, plan, carrier="fused_quant8",
            method=ef.make("ef21_sgdm", compressor=BTK, eta=0.3),
            participation=HALF)
    with pytest.raises(ValueError, match="run_async"):
        build_lib.default_ef_config(
            mesh, plan, carrier="dense",
            method=ef.make("ef21_sgdm", compressor=BTK, eta=0.3),
            participation=part_lib.Participation(mode="async"))


# ---------------------------------------------------------------------------
# kill-and-resume with a sampled cohort mid-stream
# ---------------------------------------------------------------------------

def test_kill_and_resume_sampled_cohort_bit_identical(tmp_path):
    """The cohort mask is pure in (seed, step), so a resumed run replays
    the EXACT cohort sequence: kill mid-stream, resume, and the trajectory
    (params + full ef_state) equals the uninterrupted sampled run."""
    base = RunSpec(**TINY, participation={"mode": "sampled",
                                          "fraction": 0.5, "seed": 7})
    unint = Session(base)
    unint.train(4, log_every=1)

    interrupted = Session(dataclasses.replace(base, ckpt_dir=str(tmp_path)))
    interrupted.train(2, log_every=1)
    del interrupted

    resumed = Session.resume(str(tmp_path))
    assert resumed.step == 2
    assert resumed.spec.participation == base.participation
    resumed.train(4, log_every=1)
    assert _leaves_equal(unint.params, resumed.params)
    assert _leaves_equal(unint.ef_state, resumed.ef_state)


def test_session_full_vs_sampled_fraction_one_end_to_end():
    """The whole launch stack (spec → session → build → step) preserves the
    fraction=1.0 anchor: identical params and ef_state after training."""
    full = Session(RunSpec(**TINY, carrier="sparse", compressor="topk"))
    full.train(3, log_every=1)
    sampled = Session(RunSpec(**TINY, carrier="sparse", compressor="topk",
                              participation={"mode": "sampled",
                                             "fraction": 1.0}))
    sampled.train(3, log_every=1)
    assert _leaves_equal(full.params, sampled.params)
    assert _leaves_equal(full.ef_state, sampled.ef_state)
