"""Bidirectional compression: the downlink broadcast leg (DESIGN.md §8).

Round-trip state-sync harness: after every round the server's broadcast
memory h and every client's reconstruction of it must be BIT-identical for
all (method × uplink carrier × downlink carrier) combinations — the model
everyone steps with derives from h, so h-sync IS model-sync. The harness
also anchors the regression surface (downlink='dense' must be bit-identical
to the unidirectional runtime, including the ef_state tree structure),
proves the vmap runtime against the simulator's scan loop on a deterministic
problem, and mirrors ``test_ef_recovers_quantization_error`` for the
broadcast leg (EF21-SGDM over a quant4 downlink reaches the dense-downlink
floor; the naive no-memory broadcast stalls).

Each invariant is a plain checker driven by a deterministic grid that ALWAYS
runs; a hypothesis fuzzer sweeps random shapes wherever hypothesis is
installed (the container has none — same pattern as
tests/test_carrier_properties.py).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
    settings.register_profile("bidir", max_examples=10, deadline=None)
    settings.load_profile("bidir")
except ImportError:                                   # deterministic grid only
    HAVE_HYPOTHESIS = False

from repro.core import carriers as carrier_lib
from repro.core import compressors as C
from repro.core import distributed as D
from repro.core import ef, problems, simulate
from repro.core import hierarchy as hier_lib
from repro.core import participation as part_lib

fuzz = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis fuzzing needs hypothesis "
    "(pip install -r requirements-dev.txt); the deterministic grid ran")

BTK = C.BlockTopK(block=8, k_per_block=3)
DOWN_BTK = C.BlockTopK(block=8, k_per_block=2)

# sampled (method × uplink × downlink × downlink compressor) grid — every
# downlink carrier crossed with both server modes ('delta' and 'absolute')
# and with dense/sparse/quant uplinks
GRID = [
    ("ef21_sgdm", "dense", "sparse", DOWN_BTK),
    ("ef21_sgdm", "dense", "quant8", DOWN_BTK),
    ("ef21_sgdm", "sparse", "quant4", DOWN_BTK),
    ("ef21_sgdm", "quant8", "sparse", DOWN_BTK),
    ("ef21_sgdm", "quant4", "quant4", C.Identity()),   # dense-payload quant
    ("ef21_sgd", "dense", "quant4", DOWN_BTK),
    ("ef21_sgd", "fused", "quant8", DOWN_BTK),
    ("ef14_sgd", "dense", "sparse", DOWN_BTK),         # 'absolute' server mode
    ("ef14_sgd", "sparse", "quant8", DOWN_BTK),
    ("sgdm", "dense", "quant4", DOWN_BTK),             # 'absolute', momentum
    # dense WIRE with a compressed payload: the naive-looking config that
    # still runs the full EF21 server-memory leg
    ("ef21_sgdm", "dense", "dense", C.HardThreshold(lam=0.05)),
]


def _method(name):
    kw = {"compressor": BTK}
    if name in ("ef21_sgdm", "sgdm"):
        kw["eta"] = 0.3
    return ef.make(name, **kw)


def _setup(dp=4, seed=0):
    rng = jax.random.PRNGKey(seed)
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    grads = {"w": jax.random.normal(rng, (dp, 8, 4)),
             "b": jax.random.normal(jax.random.fold_in(rng, 1), (dp, 4))}
    return params, grads


def _client_reconstruction(down_carrier, down_comp, g_new, h_old):
    """What ONE client reconstructs from the broadcast wire, recomputed
    independently of the runtime: decode the encoded wire leaf by leaf and
    integrate into its copy of h. Bit-exact agreement with the server's
    ``ef_state['h']`` is the state-sync invariant."""
    car = carrier_lib.make(down_carrier)
    plan = car.plan_down(down_comp)
    out = {}
    for k in g_new:
        delta = (g_new[k].astype(jnp.float32)
                 - h_old[k].astype(jnp.float32)).reshape(-1)
        delta = delta.astype(g_new[k].dtype)
        if plan == "wire":
            wire = car.encode(down_comp, delta)          # the broadcast bits
            dec = car.decode(down_comp, wire, d=delta.size, dtype=delta.dtype)
        else:
            dec = down_comp(delta).astype(delta.dtype)
        out[k] = (h_old[k].reshape(-1) + dec).reshape(h_old[k].shape)
    return out


# ---------------------------------------------------------------------------
# round-trip state-sync invariant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m_name,up,down,down_comp", GRID,
                         ids=[f"{m}-{u}-{d}" for m, u, d, _ in GRID])
def test_state_sync_bit_identical_every_round(m_name, up, down, down_comp):
    """After EVERY round, the server's broadcast memory h, the g_est the
    model steps with, and each client's independent reconstruction from the
    wire are all bit-identical — across the full method × carrier grid."""
    params, grads = _setup()
    method = _method(m_name)
    efc = D.EFConfig(method=method, carrier=up, down_carrier=down,
                     down_compressor=down_comp)
    st = D.init_ef_state(efc, params, 4, init_grads=grads)
    assert "h" in st
    # h⁰ = g⁰: the init handshake ships dense state once
    for k in st["server"]:
        assert np.array_equal(np.asarray(st["h"][k]),
                              np.asarray(st["server"][k]))
    rng = jax.random.PRNGKey(7)
    for t in range(3):
        g_prev_h = st["h"]
        g_est, st = D.ef_round(efc, grads, st,
                               jax.random.fold_in(rng, t))
        # the estimate everyone steps with IS the broadcast memory
        for k in st["h"]:
            assert np.array_equal(np.asarray(g_est[k]),
                                  np.asarray(st["h"][k])), (t, k)
        # a client's independent decode of the wire lands on the same h —
        # and because the reconstruction is a deterministic function of the
        # broadcast bits alone (nothing client-specific enters), one client
        # standing in for all n IS the invariant, not a shortcut
        recon = _client_reconstruction(down, down_comp, st["server"],
                                       g_prev_h)
        for k in st["h"]:
            assert np.array_equal(np.asarray(recon[k]),
                                  np.asarray(st["h"][k])), (t, k)


@pytest.mark.parametrize("m_name", ["ef21_sgdm", "ef14_sgd"])
def test_downlink_dense_is_bit_identical_to_main(m_name):
    """Regression anchor: downlink='dense' (no downlink compressor) must be
    byte-for-byte the pre-downlink runtime — same ef_state tree structure (no
    'h' sibling) and a bit-identical multi-step production trajectory."""
    from repro.optim import optimizer as opt_lib

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    rng = jax.random.PRNGKey(0)
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    x = jax.random.normal(rng, (16, 8))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (8, 4))
    batch = {"x": x, "y": x @ w}
    dp = 4
    method = _method(m_name)

    trajs = {}
    for tag, efc in [
            ("default", D.EFConfig(method=method, carrier="sparse")),
            ("explicit", D.EFConfig(method=method, carrier="sparse",
                                    down_carrier="dense",
                                    down_compressor=None))]:
        assert not efc.has_downlink
        opt = opt_lib.sgd(0.2)
        step = jax.jit(D.make_train_step(loss_fn, efc, opt, dp))
        _, _, g0 = D.per_client_value_and_grad(loss_fn, params, batch, dp)
        st = D.init_ef_state(efc, params, dp, init_grads=g0)
        assert "h" not in st
        p, os_ = params, opt.init(params)
        servers = []
        for t in range(10):
            p, os_, st, _ = step(p, os_, st, batch,
                                 jax.random.fold_in(rng, t), t)
            servers.append(np.asarray(st["server"]["w"]))
        trajs[tag] = np.stack(servers)
    assert np.array_equal(trajs["default"], trajs["explicit"])


def test_downlink_dense_identity_wire_tracks_server():
    """A bidirectional round that compresses nothing (dense wire, Identity
    compressor) reconstructs the unidirectional estimate up to float
    cancellation — h ← h + (g − h) is an ulp off g, never more — while the
    server/client h agreement stays bit-exact (the invariant above)."""
    params, grads = _setup()
    method = _method("ef21_sgdm")
    base = D.EFConfig(method=method, carrier="dense")
    bidir = D.EFConfig(method=method, carrier="dense",
                       down_carrier="dense", down_compressor=C.Identity())
    assert bidir.has_downlink
    st_b = D.init_ef_state(base, params, 4, init_grads=grads)
    st_d = D.init_ef_state(bidir, params, 4, init_grads=grads)
    for t in range(3):
        g_b, st_b = D.ef_round(base, grads, st_b, None)
        g_d, st_d = D.ef_round(bidir, grads, st_d, None)
        for k in g_b:
            np.testing.assert_allclose(
                np.asarray(g_b[k]), np.asarray(g_d[k]), rtol=1e-6,
                atol=1e-6, err_msg=k)
            np.testing.assert_allclose(
                np.asarray(st_d["h"][k]), np.asarray(st_d["server"][k]),
                rtol=1e-6, atol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# runtime agreement: the simulator's scan loop vs the vmap runtime
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _DetQuadratic:
    """Deterministic problem (stoch_grad ignores rng): makes the simulator
    and a hand-rolled ef_round loop comparable step by step."""
    d: int = 6

    def init_x(self):
        return jnp.arange(1.0, self.d + 1.0, dtype=jnp.float32)

    def full_grad(self, x):
        return x                                        # f(x) = ‖x‖²/2

    def stoch_grad(self, x, client, rng, batch):
        shift = (jnp.arange(self.d) == (client % self.d))
        return x + 0.2 * (client + 1.0) * shift.astype(jnp.float32)

    def loss(self, x):
        return 0.5 * jnp.sum(x * x)


_HALF = part_lib.Participation(mode="sampled", fraction=0.5, seed=3)
# a cross hop lossy enough that the trajectory leaves the flat one
_TWO_PODS = hier_lib.Hops(pods=2, cross_carrier="sparse",
                          cross_compressor=C.TopK(k=1))
# the fused wire's kernel (interpreted on the CPU) at a whole 128-lane block
_LANE_BTK = C.BlockTopK(block=128, k_per_block=8)


@pytest.mark.parametrize("up,down,down_comp,extra", [
    ("dense", "dense", None, {}),
    ("dense", "quant4", C.BlockTopK(block=2, k_per_block=1), {}),
    ("sparse", "sparse", C.BlockTopK(block=2, k_per_block=1), {}),
    ("quant8", "quant8", C.Identity(), {}),
    ("fused_quant8", "dense", None, {"d": 256, "comp": _LANE_BTK}),
    ("sparse", "quant8", C.Identity(), {"n": 4, "participation": _HALF}),
    ("quant8", "dense", None, {"n": 4, "hops": _TWO_PODS}),
], ids=["no-downlink", "dense-q4", "sparse-sparse", "q8-q8", "fused-q8",
        "sampled-cohort", "two-pods"])
def test_simulate_matches_ef_round_loop(up, down, down_comp, extra):
    """core/simulate.py drives core/distributed.py's vmap round — including
    the downlink ordering (x steps with h, server integrates the broadcast
    AFTER the uplink aggregate), the sampled cohort (step-indexed) and the
    pod tier: the simulator's whole trajectory equals a hand-rolled loop
    over ``ef_round`` on a deterministic problem."""
    prob = _DetQuadratic(d=extra.get("d", 6))
    n, gamma, steps = extra.get("n", 3), 1e-2, 12
    method = ef.EF21SGDM(
        compressor=extra.get("comp", C.BlockTopK(block=2, k_per_block=1)),
        eta=0.2)
    part, hops = extra.get("participation"), extra.get("hops")
    cfg = simulate.SimConfig(n=n, batch_size=1, gamma=gamma, steps=steps,
                             carrier=up, down_carrier=down,
                             down_compressor=down_comp, participation=part,
                             hops=hops)
    out = simulate.run_numpy(prob, method, cfg, seed=0)

    clients = jnp.arange(n)
    x = prob.init_x()
    g0 = jax.vmap(lambda c: prob.stoch_grad(x, c, None, 1))(clients)
    efc = D.EFConfig(method=method, carrier=up, down_carrier=down,
                     down_compressor=down_comp, participation=part,
                     hops=hops)
    assert efc == cfg.ef_config(method)
    st = D.init_ef_state(efc, x, n, init_grads=g0)
    g_use = st["h"] if efc.has_downlink else st["server"]
    gns = []
    for t in range(steps):
        x = x - gamma * g_use
        grads = jax.vmap(lambda c: prob.stoch_grad(x, c, None, 1))(clients)
        g_use, st = D.ef_round(efc, grads, st, None, step=jnp.int32(t))
        gns.append(float(jnp.sum(jnp.square(prob.full_grad(x)))))
    np.testing.assert_allclose(out["grad_norm_sq"], np.asarray(gns),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(out["x_final"]), np.asarray(x),
                               rtol=1e-5, atol=1e-7)


_COMPOSITION_ERRORS = {
    "sampled-fused-wire": ({"carrier": "fused_quant8",
                            "participation": _HALF},
                           "sampled participation cannot run the fused_wire"),
    "sampled-hops": ({"carrier": "sparse", "participation": _HALF,
                      "hops": hier_lib.Hops(pods=2)},
                     "does not compose with hierarchical"),
    "fused-wire-hops": ({"carrier": "fused_quant8",
                         "hops": hier_lib.Hops(pods=2)},
                        "no per-pod message"),
}


@pytest.mark.parametrize("error", sorted(_COMPOSITION_ERRORS))
@pytest.mark.parametrize("runtime", ["simulate", "ef_round"])
def test_runtimes_refuse_composition_errors(runtime, error):
    """Both vmap runtimes refuse the three forbidden compositions with the
    one check (``distributed.check_composition``) before any round runs."""
    fields, match = _COMPOSITION_ERRORS[error]
    prob, n = _DetQuadratic(d=256), 4
    method = ef.EF21SGDM(compressor=_LANE_BTK, eta=0.2)
    with pytest.raises(ValueError, match=match):
        if runtime == "simulate":
            simulate.run(prob, method,
                         simulate.SimConfig(n=n, steps=1, **fields),
                         jax.random.PRNGKey(0))
        else:
            efc = D.EFConfig(method=method, **fields)
            x = prob.init_x()
            grads = jax.vmap(lambda c: prob.stoch_grad(x, c, None, 1))(
                jnp.arange(n))
            st = D.init_ef_state(efc, x, n, init_grads=grads)
            D.ef_round(efc, grads, st, None, step=jnp.int32(0))


# ---------------------------------------------------------------------------
# wire accounting: the up/down split
# ---------------------------------------------------------------------------

def test_simulator_reports_downlink_wire_split():
    prob = problems.QuadraticT1()
    method = ef.EF21SGDM(compressor=C.TopK(k=1), eta=0.5)
    # d = 2, n = 2. Downlink TopK(k=1) over quant8: 1 scale + 1 quantized
    # value (1/4 word) + 1 int16 block-local index (1/2 word) = 1.75 words
    cfg = simulate.SimConfig(n=2, steps=3, down_carrier="quant8",
                             down_compressor=C.TopK(k=1))
    out = simulate.run_numpy(prob, method, cfg, seed=0)
    assert out["wire_words_per_round"] == out["wire_words_up_per_round"] == 4.0
    assert out["wire_words_down_per_round"] == 1.75 * 2
    assert out["wire_words_total_per_round"] == 4.0 + 3.5
    # without a downlink carrier the broadcast is honest dense-f32: d words
    out_d = simulate.run_numpy(
        prob, method, simulate.SimConfig(n=2, steps=3), seed=0)
    assert out_d["wire_words_down_per_round"] == 2.0 * 2
    assert out_d["wire_words_total_per_round"] == 4.0 + 4.0


def test_downlink_words_and_direction_accounting():
    d = 4096
    btk = C.BlockTopK(block=1024, k_per_block=16)
    for name in ("sparse", "quant8", "quant4"):
        car = carrier_lib.make(name)
        assert carrier_lib.downlink_words(car, btk, d) == \
            car.wire_words(btk, d)
    # degraded plans ship the dense broadcast: d words
    assert carrier_lib.downlink_words(
        carrier_lib.make("sparse"), C.Identity(), d) == d
    assert carrier_lib.downlink_words(
        carrier_lib.make("quant8"), C.RandK(), d) == d
    assert carrier_lib.downlink_words(
        carrier_lib.make("dense"), btk, d) == d
    # coords_per_message grows a direction: 'down' counts ONE broadcast of
    # the (possibly different) downlink compressor, even for Neolithic's
    # R-round uplink
    m = ef.EF21SGDM(compressor=btk)
    assert m.coords_per_message(d, carrier="quant4", direction="down") == \
        carrier_lib.make("quant4").wire_words(btk, d)
    small = C.BlockTopK(block=1024, k_per_block=4)
    assert m.coords_per_message(d, carrier="sparse", direction="down",
                                compressor=small) == \
        carrier_lib.make("sparse").wire_words(small, d)
    neo = ef.Neolithic(compressor=btk, rounds=4)
    assert neo.coords_per_message(d, carrier="sparse", direction="down") == \
        carrier_lib.make("sparse").wire_words(btk, d)          # NOT 4×


def test_downlink_plan_reasons():
    for name in ("quant8", "quant4"):
        car = carrier_lib.make(name)
        assert car.plan_down(BTK) == "wire"
        assert car.plan_down(C.Identity()) == "wire"     # dense payload
        plan, reason = car.plan_down_with_reason(C.RandK())
        assert plan == "dense" and "randomness" in reason
    plan, reason = carrier_lib.make("sparse").plan_down_with_reason(
        C.Identity())
    assert plan == "dense" and reason
    plan, reason = carrier_lib.make("fused").plan_down_with_reason(BTK)
    assert plan == "dense" and "UPLINK" in reason
    assert carrier_lib.make("dense").plan_down_with_reason(BTK) == \
        ("dense", "")


# ---------------------------------------------------------------------------
# property checkers (deterministic grid always; hypothesis fuzz when present)
# ---------------------------------------------------------------------------

def _check_downlink_roundtrip(d, down, down_comp, seed):
    """(a) the decode every client integrates equals the server's own
    integration bit-exactly; (b) downlink_round is deterministic (the same
    wire decodes identically however often a client replays it)."""
    rng = np.random.RandomState(seed)
    g = jnp.asarray(rng.randn(d).astype(np.float32))
    h = jnp.asarray(rng.randn(d).astype(np.float32))
    car = carrier_lib.make(down)
    dec1 = carrier_lib.downlink_round(car, down_comp, g - h)
    dec2 = carrier_lib.downlink_round(car, down_comp, g - h)
    assert np.array_equal(np.asarray(dec1), np.asarray(dec2))
    _, h_new = ef.downlink_sync(car, down_comp, g, h)
    assert np.array_equal(np.asarray(h + dec1), np.asarray(h_new))
    # Identity over the dense wire reconstructs g up to float cancellation
    # (h + (g − h) is an ulp off g when magnitudes differ — what stays
    # BIT-exact is the server/client agreement above, never the target)
    _, h_exact = ef.downlink_sync(carrier_lib.make("dense"), C.Identity(),
                                  g, h)
    np.testing.assert_allclose(np.asarray(h_exact), np.asarray(g),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("d", [1, 12, 50, 257])
@pytest.mark.parametrize("down,down_comp", [
    ("sparse", C.BlockTopK(block=12, k_per_block=5)),
    ("quant8", C.BlockTopK(block=12, k_per_block=5)),
    ("quant4", C.Identity()),
    ("dense", C.TopK(ratio=0.3)),
])
def test_downlink_roundtrip_grid(d, down, down_comp):
    _check_downlink_roundtrip(d, down, down_comp, seed=d)


if HAVE_HYPOTHESIS:
    @fuzz
    @given(d=st.integers(min_value=1, max_value=300),
           down=st.sampled_from(["sparse", "quant8", "quant4", "dense"]),
           seed=st.integers(min_value=0, max_value=2 ** 16))
    def test_downlink_roundtrip_fuzz(d, down, seed):
        _check_downlink_roundtrip(
            d, down, C.BlockTopK(block=12, k_per_block=5), seed)


# ---------------------------------------------------------------------------
# paper claims on the broadcast leg (slow tier — mirrors
# test_ef_recovers_quantization_error for the downlink)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_downlink_ef_recovers_compression_error():
    """EF21-SGDM with a quant4 DOWNLINK reaches the dense-downlink loss
    floor on the quadratic problem: the server memory h absorbs the
    broadcast compression error and re-sends it (the same contraction that
    makes uplink EF21 work). The naive broadcast WITHOUT server memory
    (ship the quant4 wire of g itself every round) stalls orders of
    magnitude higher — nothing re-sends the truncated mass."""
    prob = problems.RandomQuadratics(n=8, d=40, lam=0.05, sigma=1e-3, seed=0)
    sgdm = ef.EF21SGDM(compressor=C.BlockTopK(block=8, k_per_block=2),
                       eta=0.1)
    down = C.BlockTopK(block=8, k_per_block=1)
    kw = dict(n=8, batch_size=1, gamma=5e-2, steps=2500)

    def end(**cfg_kw):
        cfg = simulate.SimConfig(**kw, **cfg_kw)
        out = simulate.run_numpy(prob, sgdm, cfg, seed=0)
        return out["grad_norm_sq"][-300:].mean()

    end_dense = end()
    end_q4 = end(down_carrier="quant4", down_compressor=down)
    end_naive = end(down_carrier="quant4", down_compressor=down,
                    down_memory=False)
    # the bidirectional run sits on the same σ² noise floor as dense-down...
    assert end_q4 < 2 * end_dense, (end_q4, end_dense)
    # ...while the memory-less broadcast stalls far above it (measured
    # ~100×; 30× keeps seed headroom)
    assert end_naive > 30 * end_q4, (end_naive, end_q4)
