"""Replica worker-process tests (launch/replica_worker.py + ProcessFleet):
the §12 anchor invariant ACROSS A PROCESS BOUNDARY — a worker process joins
the wire via checkpoint + replay and its served params digest-match the
trainer's snapshot at every synced step, survives kill-and-restart
bit-identically, applies fresh records BETWEEN decode steps (continuous
sync), and a ProcessFleet completes every request even when a worker is
killed mid-run (the in-flight batch is requeued, never dropped)."""
import threading

import jax
import numpy as np
import pytest

from repro.launch import fleet as fleet_lib
from repro.launch import replica_worker as worker_lib
from repro.launch.session import Session
from repro.launch.spec import RunSpec

TINY = dict(arch="smollm-360m", smoke=True, clients=2, global_batch=4,
            seq_len=32)
QUANT4 = dict(compressor="block_topk", ratio=0.1,
              downlink_carrier="quant4", downlink_ratio=0.05)


@pytest.fixture(scope="module")
def wire(tmp_path_factory):
    """A quant4 stream with 3 published steps; the trainer session stays
    alive so tests can extend the stream mid-decode."""
    root = tmp_path_factory.mktemp("wire_rw")
    sess = Session(RunSpec(**TINY, **QUANT4))
    sess.publish_to(str(root), bootstrap_every=2)
    snaps = {}
    for _ in range(3):
        sess.step_once()
        snaps[sess.step] = jax.device_get(sess.params)
    return {"dir": str(root), "sess": sess, "snaps": snaps}


@pytest.fixture(scope="module")
def worker(wire):
    w = worker_lib.WorkerHandle(wire["dir"], name="w0", lag=0,
                                bootstrap_step=0, prompt_len=8)
    w.wait_ready()
    yield w
    w.stop()


# ---------------------------------------------------------------------------
# digest — the cross-process identity check
# ---------------------------------------------------------------------------

def test_params_digest_is_bitwise():
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.ones(4, np.int32)}
    same = {"a": tree["a"].copy(), "b": tree["b"].copy()}
    assert worker_lib.params_digest(tree) == worker_lib.params_digest(same)
    flipped = {"a": tree["a"].copy(), "b": tree["b"].copy()}
    flipped["a"][1, 2] = np.nextafter(flipped["a"][1, 2],
                                      np.float32(np.inf))  # exactly one ulp
    assert worker_lib.params_digest(tree) != worker_lib.params_digest(flipped)
    recast = {"a": tree["a"].astype(np.float64), "b": tree["b"]}
    assert worker_lib.params_digest(tree) != worker_lib.params_digest(recast)


# ---------------------------------------------------------------------------
# one worker process: sync, digest, heartbeat, continuous sync
# ---------------------------------------------------------------------------

def test_worker_syncs_bit_identical_to_trainer(wire, worker):
    """The tier-1 anchor: sync the worker to the head and compare its params
    digest against the trainer's in-memory snapshot — equal digests ⟺
    bit-identical trees, proven across the process boundary."""
    head = max(wire["snaps"])
    r = worker.call({"cmd": "sync", "upto": head})
    assert r["step"] == head
    d = worker.call({"cmd": "digest"})
    assert d["digest"] == worker_lib.params_digest(wire["snaps"][head])


def test_worker_heartbeats_and_reports_step(worker):
    worker.call({"cmd": "sync"})               # ensure at least one hb cycle
    deadline = threading.Event()
    deadline.wait(0.6)                         # > 2 heartbeat intervals
    assert worker.hb_age() < 5.0
    assert worker.step is not None


def test_worker_rejects_unknown_command(worker):
    with pytest.raises(RuntimeError, match="unknown cmd"):
        worker.call({"cmd": "frobnicate"})


@pytest.mark.slow
def test_worker_continuous_sync_during_decode(wire, worker):
    """Publish fresh steps AFTER the worker synced, then serve with
    ``sync_during_decode``: the decode hook must apply them mid-batch
    (``mid_applied`` > 0) and the worker finishes ON the new head — a long
    decode never pins the batch to the params it started with."""
    worker.call({"cmd": "sync"})
    sess = wire["sess"]
    for _ in range(2):
        sess.step_once()
        wire["snaps"][sess.step] = jax.device_get(sess.params)
    head = sess.step
    r = worker.call({"cmd": "serve", "requests": [
        {"rid": 0, "tokens": list(range(8)), "max_new_tokens": 4},
        {"rid": 1, "tokens": [0, 7, 0], "max_new_tokens": 4}],
        "decode_steps": 4, "prompt_len": 8, "sync_during_decode": True})
    assert r["step"] == head
    assert r["mid_applied"] >= 1
    assert r["tokens_generated"] == [4, 4]
    assert all(len(t) == 4 for t in r["tokens"])
    d = worker.call({"cmd": "digest"})
    assert d["digest"] == worker_lib.params_digest(wire["snaps"][head])


@pytest.mark.slow
def test_worker_kill_and_restart_bit_identity(wire, worker):
    """Kill -9 the worker and restart it: the fresh process rejoins via
    checkpoint + replay and must return the SAME digest — the anchor
    invariant survives a crash."""
    worker.call({"cmd": "sync"})
    before = worker.call({"cmd": "digest"})["digest"]
    head = max(wire["snaps"])
    assert before == worker_lib.params_digest(wire["snaps"][head])
    worker.kill()
    assert not worker.alive()
    worker.restart()
    worker.call({"cmd": "sync"})
    after = worker.call({"cmd": "digest"})["digest"]
    assert after == before
    assert worker.restarts == 1


# ---------------------------------------------------------------------------
# the multi-process fleet
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_process_fleet_serves_and_survives_kill(wire):
    """Two worker processes on one stream: every request completes across
    both workers; then a worker is killed mid-run — its in-flight batch is
    requeued at the front, the worker restarts, and every request STILL
    completes, with the restart surfaced in the summary."""
    with fleet_lib.ProcessFleet(wire["dir"], n_workers=2, lags=(0, 2),
                                decode_budget=8, max_batch=2,
                                prompt_len=8) as fl:
        fl.sync()
        steps = [w.call({"cmd": "sync"})["step"] for w in fl.workers]
        assert steps[0] - steps[1] == 2        # lags honored
        reqs = fleet_lib.synthetic_requests(6, rate=50.0, prompt_len=8,
                                            max_new_tokens=4)
        out = fl.run(reqs)
        assert sorted(r.rid for r in out["requests"]) == list(range(6))
        assert {r.replica for r in out["requests"]} == {"w0", "w1"}
        assert out["restarts"] == 0
        assert out["short_requests"] == 0
        assert all(r.tokens_generated == 4 for r in out["requests"])
        assert out["p50_ms"] <= out["p99_ms"]

        killer = threading.Timer(0.2, fl.workers[1].kill)
        killer.start()
        reqs = fleet_lib.synthetic_requests(6, rate=20.0, prompt_len=8,
                                            max_new_tokens=4, seed=1)
        out = fl.run(reqs)
        killer.cancel()
        assert sorted(r.rid for r in out["requests"]) == list(range(6))
        assert out["restarts"] >= 1


def test_process_fleet_refuses_a_held_or_shared_chip(monkeypatch, tmp_path):
    """One process per chip: ProcessFleet raises BEFORE spawning workers
    that would need an accelerator this process holds, or one accelerator
    between several of them — such a worker fails or hangs on the chip.
    A host with no accelerator, or workers forced onto the CPU, start any
    number of workers."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(fleet_lib, "accelerator_nodes", lambda: [])
    assert fleet_lib.can_spawn_workers(2) is None   # CPU-only host

    monkeypatch.setattr(fleet_lib, "accelerator_nodes",
                        lambda: ["/dev/vfio/0"])
    monkeypatch.setattr(fleet_lib, "held_nodes", lambda nodes: list(nodes))
    with pytest.raises(RuntimeError, match="this process holds"):
        fleet_lib.ProcessFleet(str(tmp_path), n_workers=1)
    monkeypatch.setattr(fleet_lib, "held_nodes", lambda nodes: [])
    assert "would share" in fleet_lib.can_spawn_workers(2)
    assert fleet_lib.can_spawn_workers(1) is None   # one worker takes it
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert fleet_lib.can_spawn_workers(2) is None   # every worker on CPU


def test_held_nodes_reads_this_process_open_files(tmp_path):
    """How a held chip is seen: its device node among this process's open
    files (a plain file stands in for the node here)."""
    node = tmp_path / "accel0"
    node.write_text("")
    assert fleet_lib.held_nodes([str(node)]) == []
    with open(node):
        assert fleet_lib.held_nodes([str(node)]) == [str(node)]
