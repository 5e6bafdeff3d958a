"""Wire-carrier subsystem (core/carriers.py): dense / sparse / fused carriers
must produce the same g_server trajectories (the wire format is transport, not
semantics), and wire_words accounting must stay honest."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import carriers as carrier_lib
from repro.core import compressors as C
from repro.core import distributed as D
from repro.core import ef, problems, simulate
from repro.optim import optimizer as opt_lib


def loss_fn(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2), {}


@pytest.fixture
def setup():
    rng = jax.random.PRNGKey(0)
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    x = jax.random.normal(rng, (16, 8))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (8, 4))
    return params, {"x": x, "y": x @ w}


BLOCK_TOPK = C.BlockTopK(block=8, k_per_block=3)


def _trajectory(setup, method, carrier, steps=40, cache=None):
    """g_server / loss trajectory of the production train step. ``cache`` is
    the session-scoped step_cache fixture: the jitted step for a given
    (method, carrier, dp) compiles once per test session."""
    params, batch = setup
    dp = 4
    lr = 0.2
    efc = D.EFConfig(method=method, carrier=carrier)
    opt = opt_lib.sgd(lr)
    # the key must cover everything the jitted step closes over (step_cache
    # is shared session-wide)
    key = (loss_fn, "sgd", lr, method, carrier, dp)
    if cache is None or key not in cache:
        step = jax.jit(D.make_train_step(loss_fn, efc, opt, dp))
        if cache is not None:
            cache[key] = step
    else:
        step = cache[key]
    _, _, g0 = D.per_client_value_and_grad(loss_fn, params, batch, dp)
    p, os_, es = params, opt.init(params), D.init_ef_state(
        efc, params, dp, init_grads=g0)
    rng = jax.random.PRNGKey(1)
    servers = []
    for t in range(steps):
        p, os_, es, m = step(p, os_, es, batch, jax.random.fold_in(rng, t), t)
        servers.append(np.asarray(es["server"]["w"]))
    return np.stack(servers)


@pytest.mark.parametrize("carrier", ["sparse", "fused"])
@pytest.mark.parametrize("method_name", ["ef21_sgdm", "ef21_sgd"])
def test_train_step_g_server_matches_dense(setup, carrier, method_name,
                                           step_cache):
    """Every carrier is a pure transport: the server estimate gᵗ it produces
    over a full training run must equal the dense (paper-faithful) one up to
    float/tie tolerance."""
    kwargs = {"compressor": BLOCK_TOPK}
    if method_name == "ef21_sgdm":
        kwargs["eta"] = 0.3
    method = ef.make(method_name, **kwargs)
    ref = _trajectory(setup, method, "dense", cache=step_cache)
    got = _trajectory(setup, method, carrier, cache=step_cache)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("carrier", ["sparse", "fused"])
def test_simulator_matches_dense_on_quadratic(carrier):
    """Both runtimes share one carrier implementation — the simulator's
    whole trajectory (through the vmap round) on a quadratic problem must
    match dense."""
    prob = problems.QuadraticT1()
    method = ef.EF21SGDM(compressor=C.BlockTopK(block=2, k_per_block=1),
                         eta=0.2)
    out = {}
    for c in ("dense", carrier):
        cfg = simulate.SimConfig(n=4, batch_size=2, gamma=1e-2, steps=200,
                                 carrier=c)
        out[c] = simulate.run_numpy(prob, method, cfg, seed=0)
    np.testing.assert_allclose(out[carrier]["grad_norm_sq"],
                               out["dense"]["grad_norm_sq"],
                               rtol=1e-4, atol=1e-7)


def test_fused_degrades_to_dense_plan_when_unfusable():
    fused = carrier_lib.make("fused")
    assert fused.plan(ef.EF21SGDM(compressor=C.BlockTopK())) == "fused"
    # TopK is not the kernel's compressor; traced η can't be baked in
    assert fused.plan(ef.EF21SGDM(compressor=C.TopK())) == "dense"
    assert fused.plan(ef.EF21SGDM(compressor=C.BlockTopK()),
                      eta=jnp.float32(0.1)) == "dense"
    assert fused.plan(ef.EF14SGD(compressor=C.BlockTopK())) == "dense"


def test_every_method_carrier_pair_roundtrips_or_reports_why():
    """Carrier.plan used to degrade to 'dense' silently — a misconfigured run
    looked identical to a working one in logs. Every (method × carrier) pair
    must now either run the carrier's native plan or return a non-empty
    plan_reason explaining the degradation (launch/build.py warns with it,
    launch/train.py prints it)."""
    comp = C.BlockTopK(block=8, k_per_block=3)
    # each carrier's native (most-fused) plan: a reason is non-empty iff the
    # executed plan is anything less — dense for most carriers; fused_quant
    # additionally reports its fall-back to the unfused quantized 'wire'
    native = {"dense": "dense", "sparse": "wire", "fused": "fused",
              "quant8": "wire", "quant4": "wire",
              "fused_quant8": "fused_wire", "fused_quant4": "fused_wire"}
    assert set(native) == set(carrier_lib.REGISTRY)
    for m_name in ef.REGISTRY:
        method = ef.make(m_name, compressor=comp)
        for c_name in carrier_lib.REGISTRY:
            car = carrier_lib.make(c_name)
            plan, reason = car.plan_with_reason(method)
            assert plan == car.plan(method)
            if plan != native[c_name]:
                assert reason, (m_name, c_name)
            else:
                assert reason == "", (m_name, c_name, reason)


def test_quant_plan_degradations_have_reasons():
    for name in ("quant8", "quant4"):
        car = carrier_lib.make(name)
        assert car.plan(ef.EF21SGDM(compressor=BLOCK_TOPK)) == "wire"
        # dense payload: any deterministic compressor rides the wire
        assert car.plan(ef.EF21SGDM(compressor=C.HardThreshold())) == "wire"
        plan, reason = car.plan_with_reason(
            ef.EF21SGDM(compressor=C.RandK()))
        assert plan == "dense" and "randomness" in reason
        plan, reason = car.plan_with_reason(
            ef.EF21SGDMAbs(compressor=BLOCK_TOPK))
        assert plan == "dense" and "wire_is_msg" in reason


def test_quant_wire_words_fractional_accounting():
    """A 4-bit mantissa is 1/8 word, int8 is 1/4, each block ships one f32
    scale, block-local indices are int16 (1/2 word) when the block fits —
    and at equal K the quantized wires undercut the sparse carrier."""
    d = 4096
    btk = C.BlockTopK(block=1024, k_per_block=16)
    sparse, q8, q4 = (carrier_lib.make(n)
                      for n in ("sparse", "quant8", "quant4"))
    nb, kb = 4, 16
    assert q8.wire_words(btk, d) == nb * (1 + kb * (8 / 32 + 0.5))
    assert q4.wire_words(btk, d) == nb * (1 + kb * (4 / 32 + 0.5))
    assert (q4.wire_words(btk, d) < q8.wire_words(btk, d)
            < sparse.wire_words(btk, d))
    # single-block TopK on a large leaf: indices fall back to a full word
    big = 2 ** 16
    topk = C.TopK(k=8)
    assert q8.wire_words(topk, big) == 1 + 8 * (8 / 32 + 1.0)
    # dense payload: scales + packed mantissas, no indices
    ident = C.Identity()
    nbq = -(-d // q4.qblock)
    assert q4.wire_words(ident, d) == nbq * (1 + q4.qblock * 4 / 32)
    # coords_per_message delegation
    m = ef.EF21SGDM(compressor=btk)
    assert m.coords_per_message(d, carrier="quant4") == \
        q4.wire_words(btk, d)


@pytest.mark.slow
@pytest.mark.parametrize("carrier", ["quant8", "quant4"])
def test_quant_carrier_converges_like_dense_on_quadratic(carrier):
    """Quantization changes the trajectory (unlike sparse/fused, the wire is
    lossy beyond C), but EF re-sends the quantization error, so the simulator
    must reach the same gradient-norm floor as the dense wire."""
    prob = problems.QuadraticT1()
    method = ef.EF21SGDM(compressor=C.BlockTopK(block=2, k_per_block=1),
                         eta=0.2)
    out = {}
    for c in ("dense", carrier):
        cfg = simulate.SimConfig(n=4, batch_size=2, gamma=1e-2, steps=300,
                                 carrier=c)
        out[c] = simulate.run_numpy(prob, method, cfg, seed=0)
    end_d = out["dense"]["grad_norm_sq"][-50:].mean()
    end_q = out[carrier]["grad_norm_sq"][-50:].mean()
    assert end_q < 3 * end_d + 1e-6, (end_q, end_d)


def test_sparse_plan_respects_wire_is_msg():
    sparse = carrier_lib.make("sparse")
    assert sparse.plan(ef.EF21SGDM(compressor=C.TopK())) == "wire"
    assert sparse.plan(ef.EF21SGDM(compressor=C.BlockTopK())) == "wire"
    # Abs transforms c into γ·c — the wire is not the message
    assert sparse.plan(ef.EF21SGDMAbs(compressor=C.TopK())) == "dense"
    # RandK needs rng in encode; carrier degrades rather than miscompress
    assert sparse.plan(ef.EF21SGDM(compressor=C.RandK())) == "dense"


def test_wire_words_accounting():
    d = 4096
    dense, sparse, fused = (carrier_lib.make(n)
                            for n in ("dense", "sparse", "fused"))
    topk = C.TopK(ratio=0.01)
    btk = C.BlockTopK(block=1024, k_per_block=16)
    # dense/fused all-reduce ships every coordinate regardless of sparsity
    assert dense.wire_words(topk, d) == d
    assert fused.wire_words(btk, d) == d
    # sparse ships values AND int32 indices: 2× the coordinate count
    assert sparse.wire_words(topk, d) == 2 * topk._k(d)
    assert sparse.wire_words(btk, d) == 2 * (d // 1024) * 16
    # Method.coords_per_message delegates when a carrier is named
    m = ef.EF21SGDM(compressor=btk)
    assert m.coords_per_message(d) == (d // 1024) * 16          # paper x-axis
    assert m.coords_per_message(d, carrier="sparse") == \
        sparse.wire_words(btk, d)
    assert m.coords_per_message(d, carrier="dense") == d
    neo = ef.Neolithic(compressor=topk, rounds=4)
    assert neo.coords_per_message(d, carrier="sparse") == \
        4 * sparse.wire_words(topk, d)


def test_simulator_reports_wire_words():
    prob = problems.QuadraticT1()
    method = ef.EF21SGDM(compressor=C.TopK(k=1), eta=0.5)
    out = {}
    for carrier, expect in (("dense", 2.0), ("sparse", 2.0),
                            ("quant8", 1.75), ("quant4", 1.625)):
        cfg = simulate.SimConfig(n=2, steps=3, carrier=carrier)
        out[carrier] = simulate.run_numpy(prob, method, cfg, seed=0)
        # d = 2, n = 2: TopK(k=1) → 1 coord (paper), dense wire = 2 words,
        # sparse wire = 2 words (1 value + 1 int32 index), quant wires =
        # 1 scale + quantized value (1/4 | 1/8 word) + int16 index (1/2)
        assert out[carrier]["coords_per_round"] == 1 * 2
        assert out[carrier]["wire_words_per_round"] == expect * 2
    # acceptance: at equal K the quant carriers undercut the sparse wire
    assert (out["quant4"]["wire_words_per_round"]
            < out["quant8"]["wire_words_per_round"]
            < out["sparse"]["wire_words_per_round"])


def test_sparse_carrier_roundtrip_matches_compressor():
    """encode→local_c equals the compressor's dense C(x); encode→aggregate
    with one client equals it too (ties aside, none here)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(50).astype(np.float32))
    sparse = carrier_lib.make("sparse")
    for comp in (C.TopK(ratio=0.2), C.BlockTopK(block=16, k_per_block=4)):
        wire = sparse.encode(comp, x)
        c_loc = sparse.local_c(comp, x, wire)
        np.testing.assert_allclose(np.asarray(c_loc), np.asarray(comp(x)),
                                   rtol=1e-6)
        wire1 = jax.tree_util.tree_map(lambda a: a[None], wire)
        agg = sparse.aggregate(comp, wire1, d=x.size, dtype=x.dtype, dp=1)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(comp(x)),
                                   rtol=1e-6)


def test_sparse_local_c_is_exact_wire_decode():
    """On a tie at the k-th rank, local_c must keep exactly what the wire
    shipped (k entries), not the threshold mask (which would keep both tied
    coordinates and desynchronize client state from the server aggregate)."""
    x = jnp.asarray([1.0, -1.0, 0.5, 0.25], jnp.float32)   # |tie| at rank 1
    comp = C.TopK(k=1)
    sparse = carrier_lib.make("sparse")
    wire = sparse.encode(comp, x)
    c = np.asarray(sparse.local_c(comp, x, wire))
    assert (c != 0).sum() == 1
    vals, idx = (np.asarray(a).reshape(-1) for a in wire)
    np.testing.assert_allclose(c[idx[0]], vals[0])


def test_unknown_carrier_rejected():
    with pytest.raises(ValueError):
        carrier_lib.make("carrier-pigeon")


# ---------------------------------------------------------------------------
# BlockTopK geometry: sub-block and non-divisible leaves (per-group schedules
# route tiny norm/bias tensors through their own compressors, so the fixed
# full-block K must not degenerate on leaves smaller than one block)
# ---------------------------------------------------------------------------

def test_block_topk_sub_block_leaf_gets_proportional_k():
    """A (64,) leaf under ratio=0.05/block=1024 used to get the full-block
    K = round(0.05·1024) = 51 — keeping 80% of the tensor while reporting
    α = 0.05. The d-aware geometry gives one block of the leaf's own size
    and K = round(0.05·64) = 3."""
    comp = C.BlockTopK(ratio=0.05, block=1024)
    nb, block, kb = comp.geom(64)
    assert (nb, block, kb) == (1, 64, 3)
    x = jnp.asarray(np.random.RandomState(0).randn(64), jnp.float32)
    assert int((np.asarray(comp(x)) != 0).sum()) == 3
    assert comp.alpha(64) == pytest.approx(3 / 64)
    # leaves of at least one block keep the exact legacy geometry
    assert comp.geom(4096) == (4, 1024, 51)
    # explicit k_per_block is capped at the leaf size instead of selecting
    # padding zeros
    small = C.BlockTopK(block=1024, k_per_block=16)
    assert small.geom(8) == (1, 8, 8)
    # k of a 1-element leaf never hits zero
    assert C.BlockTopK(ratio=0.01, block=1024).geom(3) == (1, 3, 1)


@pytest.mark.parametrize("d", [5, 64, 100, 2500])
def test_block_topk_wire_roundtrips_on_odd_sizes(d):
    """Sub-block (d < block) and non-divisible (d % block ≠ 0) leaves:
    encode→local_c must equal the dense C(x), indices must stay in range,
    and wire_words must reflect the d-aware geometry for the sparse AND
    quantized carriers."""
    rng = np.random.RandomState(d)
    x = jnp.asarray(rng.randn(d).astype(np.float32))
    comp = C.BlockTopK(ratio=0.1, block=64)
    nb, block, kb = comp.geom(d)
    assert kb <= block <= max(d, 1)
    sparse = carrier_lib.make("sparse")
    wire = sparse.encode(comp, x)
    c_loc = np.asarray(sparse.local_c(comp, x, wire))
    np.testing.assert_allclose(c_loc, np.asarray(comp(x)), rtol=1e-6)
    assert sparse.wire_words(comp, d) == 2.0 * nb * kb
    vals, idx = wire
    assert int(np.asarray(idx).max()) < block
    # one-client aggregate equals the dense compressor output too
    wire1 = jax.tree_util.tree_map(lambda a: a[None], wire)
    agg = sparse.aggregate(comp, wire1, d=d, dtype=x.dtype, dp=1)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(comp(x)),
                               rtol=1e-6)
    # quantized sparse payload: decode == local_c (the EF invariant), and
    # the word count uses the same d-aware geometry
    quant = carrier_lib.make("quant8")
    qwire = quant.encode(comp, x)
    q_loc = np.asarray(quant.local_c(comp, x, qwire))
    np.testing.assert_allclose(
        q_loc, np.asarray(quant.decode(comp, qwire, d=d, dtype=x.dtype)))
    idx_words = 0.5 if block <= 2 ** 15 - 1 else 1.0
    assert quant.wire_words(comp, d) == nb * (1.0 + kb * (0.25 + idx_words))


def test_fused_carrier_consistent_on_sub_block_leaves(step_cache):
    """The fused kernel now runs each leaf at its d-aware (block, kb) — a
    model with sub-block bias/norm leaves must still match the dense
    trajectory (the b leaf here is smaller than the compressor block)."""
    rng = jax.random.PRNGKey(0)
    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    x = jax.random.normal(rng, (16, 8))
    w = jax.random.normal(jax.random.fold_in(rng, 1), (8, 4))
    setup = (params, {"x": x, "y": x @ w})
    method = ef.EF21SGDM(compressor=C.BlockTopK(block=16, k_per_block=3),
                         eta=0.3)
    ref = _trajectory(setup, method, "dense", steps=20, cache=step_cache)
    got = _trajectory(setup, method, "fused", steps=20, cache=step_cache)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
