"""Session-layer tests (launch/session.py): full-state checkpointing makes a
kill-and-resume run BIT-IDENTICAL to an uninterrupted one (the EF21 invariant
that server and clients agree on g survives restarts), the spec-hash guard
refuses foreign checkpoints, latest() orders numerically, and serve/lower run
through the build/shardings path on the session mesh."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt_lib
from repro.launch import session as session_lib
from repro.launch.session import Session
from repro.launch.spec import RunSpec

TINY = dict(arch="smollm-360m", smoke=True, clients=2, global_batch=4,
            seq_len=32)


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def test_kill_and_resume_is_bit_identical(tmp_path):
    """save→restore→step equals the uninterrupted run exactly: params,
    opt_state, ef_state (gᵢ, vᵢ — the old --resume dropped these, violating
    Algorithm 1's server/client agreement on g), and the logged loss
    trajectory."""
    base = RunSpec(**TINY)
    unint = Session(base)
    unint.train(6, log_every=1)

    interrupted = Session(dataclasses.replace(base, ckpt_dir=str(tmp_path)))
    interrupted.train(3, log_every=1)
    del interrupted                         # "kill" the process

    resumed = Session.resume(str(tmp_path))
    assert resumed.step == 3
    assert resumed.spec.spec_hash() == base.spec_hash()  # no flags re-passed
    resumed.train(6, log_every=1)

    assert _leaves_equal(unint.params, resumed.params)
    assert _leaves_equal(unint.opt_state, resumed.opt_state)
    assert _leaves_equal(unint.ef_state, resumed.ef_state)
    tail = [(r["step"], r["loss"], r["g_norm"]) for r in unint.history[3:]]
    got = [(r["step"], r["loss"], r["g_norm"]) for r in resumed.history]
    assert tail == got


def test_kill_and_resume_bidirectional_covers_server_memory(tmp_path):
    """The downlink server memory h (DESIGN.md §8) is part of ef_state and
    must survive a kill-and-resume bit-exactly — restoring everything BUT h
    would re-initialize the broadcast memory to g while the restored params
    are mid-trajectory, silently desynchronizing server and clients."""
    base = RunSpec(**TINY, downlink_carrier="quant4", downlink_ratio=0.1)
    unint = Session(base)
    unint.train(4, log_every=1)
    assert "h" in unint.ef_state

    interrupted = Session(dataclasses.replace(base, ckpt_dir=str(tmp_path)))
    interrupted.train(2, log_every=1)
    del interrupted

    resumed = Session.resume(str(tmp_path))
    assert resumed.step == 2
    assert resumed.spec.downlink_carrier == "quant4"
    resumed.train(4, log_every=1)
    assert _leaves_equal(unint.params, resumed.params)
    assert _leaves_equal(unint.ef_state["h"], resumed.ef_state["h"])
    assert _leaves_equal(unint.ef_state, resumed.ef_state)


def test_resume_refuses_foreign_spec_unless_overridden(tmp_path):
    spec = RunSpec(**TINY, ckpt_dir=str(tmp_path))
    sess = Session(spec)
    sess.train(2, log_every=1)

    other = dataclasses.replace(spec, lr=0.01)
    with pytest.raises(ValueError, match="different RunSpec"):
        Session.resume(str(tmp_path), spec=other)
    forced = Session.resume(str(tmp_path), spec=other,
                            allow_spec_mismatch=True)
    assert forced.step == 2 and forced.spec.lr == 0.01


def test_resume_layers_overrides_onto_embedded_spec(tmp_path):
    """'--resume --eta X' means 'the same run, new eta' — overrides layer
    onto the checkpoint's embedded spec, never onto defaults."""
    spec = RunSpec(**TINY, ckpt_dir=str(tmp_path))
    Session(spec).train(1, log_every=1)

    with pytest.raises(ValueError, match="different RunSpec"):
        Session.resume(str(tmp_path), overrides={"lr": 0.01})
    sess = Session.resume(str(tmp_path), overrides={"lr": 0.01},
                          allow_spec_mismatch=True)
    # the embedded geometry survives; only the override changed
    assert sess.spec.seq_len == 32 and sess.spec.clients == 2
    assert sess.spec.smoke and sess.spec.lr == 0.01
    # checkpoint-POLICY overrides need no mismatch approval (hash-excluded)
    sess = Session.resume(str(tmp_path), overrides={"ckpt_every": 5})
    assert sess.spec.ckpt_every == 5 and sess.spec.seq_len == 32


def test_checkpoint_latest_orders_numerically(tmp_path):
    tree = {"x": np.zeros((2,), np.float32)}
    for step in (2, 10):                   # lexicographic max() picks step_2
        ckpt_lib.save(str(tmp_path / f"step_{step}.npz"), tree, step=step)
    # a killed save leaves a mkstemp partial; it must never be selected
    (tmp_path / "tmpzz99999999.tmp.npz").write_bytes(b"partial")
    path = ckpt_lib.latest(str(tmp_path))
    assert path.endswith("step_10.npz")
    assert ckpt_lib.parse_step("step_00000010.npz") == 10
    assert ckpt_lib.parse_step("final.npz") is None


def test_save_records_spec_hash_in_meta(tmp_path):
    spec = RunSpec(**TINY, ckpt_dir=str(tmp_path))
    sess = Session(spec)
    sess.train(1, log_every=1)
    meta = ckpt_lib.read_meta(ckpt_lib.latest(str(tmp_path)))
    assert meta["spec_hash"] == spec.spec_hash()
    assert meta["step"] == sess.step        # the data cursor
    assert RunSpec.from_dict(meta["spec"]) == spec


def test_periodic_save_does_not_double_write_final_step(tmp_path):
    spec = RunSpec(**TINY, ckpt_dir=str(tmp_path), ckpt_every=2)
    sess = Session(spec)
    sess.train(4, log_every=1)              # ckpt_every divides the end step
    import os
    files = sorted(os.listdir(tmp_path))
    assert files == ["step_00000002.npz", "step_00000004.npz"]
    # ...and a later resume restores from the template path without paying a
    # fresh init (behavioral check: state round-trips exactly)
    resumed = Session.resume(str(tmp_path))
    assert resumed.step == 4
    assert _leaves_equal(resumed.params, sess.params)
    assert _leaves_equal(resumed.ef_state, sess.ef_state)


def test_failed_restore_leaves_session_usable(tmp_path):
    """A restore that dies mid-way (shape mismatch under forced resume) must
    not leave abstract ShapeDtypeStruct templates behind — the session still
    trains from a fresh init afterwards."""
    other = Session(RunSpec(arch="h2o-danube-3-4b", smoke=True, clients=2,
                            global_batch=4, seq_len=32,
                            ckpt_dir=str(tmp_path)))
    other.train(1, log_every=1)

    sess = Session(RunSpec(**TINY))
    with pytest.raises((ValueError, KeyError)):
        sess.restore_from(ckpt_lib.latest(str(tmp_path)),
                          allow_spec_mismatch=True)
    sess.train(1, log_every=1)              # fresh init, not template leaves
    assert np.isfinite(sess.history[-1]["loss"])


def test_evaluate_and_method_accessors():
    sess = Session(RunSpec(**TINY))
    loss = sess.evaluate(batches=1)
    assert np.isfinite(loss) and loss > 0
    assert sess.method.name == "ef21_sgdm"
    assert sess.n_clients == 2


def test_serve_runs_through_build_shardings():
    sess = Session(RunSpec(**TINY))
    out = sess.serve(batch=2, prompt_len=16, decode_steps=2)
    assert out["tokens"].shape == (2, 3)    # first token + 2 decode steps
    assert out["cache_bytes"] > 0


def test_serve_params_track_same_step_state_changes(tmp_path):
    """Regression for the step-keyed serve cache: ``_serve_params`` used to
    key on the step counter, so restoring state or injecting a subscriber
    tree WITHOUT moving the step served stale params. The cache now keys on
    ``_params_version`` — the single source of truth every mutation path
    (step_once, restore_from, set_serve_params) bumps."""
    import jax.numpy as jnp

    sess = Session(RunSpec(**TINY, ckpt_dir=str(tmp_path)))
    sess.train(2)                                # checkpoints at step 2
    path = ckpt_lib.latest(str(tmp_path))
    sess.serve(batch=1, prompt_len=8, decode_steps=1)
    trained = jax.device_get(sess._serve_params[1])

    # inject a different tree at the SAME step (the wire-subscriber path):
    # the step counter does not move, the served params must
    zeros = jax.tree_util.tree_map(jnp.zeros_like, sess.params)
    sess.set_serve_params(zeros)
    sess.serve(batch=1, prompt_len=8, decode_steps=1)
    assert all(not np.any(np.asarray(leaf)) for leaf in
               jax.tree_util.tree_leaves(sess._serve_params[1]))

    # restore at the SAME step: the injected tree is superseded and serve
    # returns to the checkpoint's params without the step counter moving
    sess.restore_from(path)
    assert sess.step == 2
    sess.serve(batch=1, prompt_len=8, decode_steps=1)
    assert _leaves_equal(sess._serve_params[1], trained)


def test_lower_produces_dryrun_artifact_on_smoke_mesh():
    sess = Session(RunSpec(**TINY, carrier="sparse"))
    with sess.mesh_context():
        lowered = sess.lower()              # custom train shape, 1-device mesh
        hlo = lowered.as_text()
    assert "while" in hlo or "fusion" in hlo or len(hlo) > 1000


def test_make_method_rejects_unknown_kwargs():
    with pytest.raises(ValueError, match="method_kw"):
        session_lib.make_method(RunSpec(method_kw={"bogus_knob": 1}))
    with pytest.raises(ValueError, match="compressor_kw"):
        session_lib.make_compressor(RunSpec(compressor_kw={"nope": 2}))


def test_compile_cache_honours_env_else_one_fixed_path(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins when set (the code sets nothing);
    otherwise the cache sits at one fixed, git-ignored path in the
    checkout — the path is part of the cache key, so it never moves."""
    import os
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.CACHE_DIR == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
