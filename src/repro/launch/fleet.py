"""Serving fleet fed by the downlink wire (DESIGN.md §12).

A ``ServeReplica`` is a serving ``Session`` whose parameters are kept
bit-identical to the trainer's by SUBSCRIBING to the wire stream a training
session publishes (``Session.publish_to`` → core/stream.py): it joins from
the stream's bootstrap checkpoint, replays every record (checkpoint +
replay), and between request batches applies new records through the exact
train-step tail — never a dense f32 weight push. A ``Fleet`` runs several
replicas against ONE stream at different lags behind the trainer head,
dispatching a request queue through a decode-budget scheduler:

    sess = Session(spec); sess.publish_to("/tmp/wire"); sess.train(100)
    fleet = Fleet("/tmp/wire", n_replicas=2, lags=(0, 4))
    results = fleet.run(synthetic_requests(32, rate=8.0))

Scheduling: requests are admitted FIFO into one serving batch while
``B × decode_steps ≤ decode_budget`` (decode steps bucketed to powers of two
so the jitted serve geometries stay bounded — ``Session.serve`` caches its
compiled prefill/decode per (B, S, D)). The per-arch serving carve-outs of
DESIGN.md §5 (sliding-window caches, prefix-embed frontends) are enforced by
``build_prefill``/``build_decode`` underneath ``Session.serve``; the
scheduler's job is only to keep every serving step inside the decode budget
those builds were sized for.

Staleness: a replica at lag L serves the trainer's step-(head−L) model —
exact, never drifted (gaps resync via a later bootstrap, or fail loudly).
This is SERVING staleness, distinct from the async TRAINING staleness cap of
DESIGN.md §11 — see §12 for the contrast.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import time
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.checkpoint import checkpoint as ckpt_lib
from repro.core import stream as stream_lib
from repro.launch import session as session_lib
from repro.launch import spec as spec_lib
from repro.launch import transport as transport_lib
from repro.models import model as model_lib
from repro.optim import optimizer as opt_lib

PyTree = Any


# ---------------------------------------------------------------------------
# requests + decode-budget scheduler
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One serving request. ``arrival_s`` is relative to the run's t0; the
    completion fields are filled by ``Fleet.run``."""

    rid: int
    tokens: np.ndarray                  # 1-D prompt token ids
    max_new_tokens: int = 16
    arrival_s: float = 0.0
    # filled on completion
    t_done: float = 0.0
    latency_s: float = 0.0
    replica: str = ""
    staleness: int = 0
    tokens_out: Optional[np.ndarray] = None
    tokens_generated: int = 0           # may be < max_new_tokens (capped)


def finalize_request(req: Request, row) -> None:
    """Fill a request's generated tokens from one served row: at most
    ``max_new_tokens`` tokens, and ``tokens_generated`` records how many the
    decode budget actually allowed — an oversized lone request admitted with
    capped decode completes SHORT, and the shortfall must be visible on the
    request (and in ``run()``'s summary), never silently swallowed."""
    avail = np.asarray(row)
    take = min(req.max_new_tokens, int(avail.size))
    req.tokens_out = avail[:take]
    req.tokens_generated = take


def _bucket(n: int) -> int:
    """Next power of two ≥ n — decode geometries are bucketed so the jitted
    serve cache stays small (log2 many entries, not one per request mix)."""
    return 1 << max(0, int(n - 1).bit_length())


@dataclasses.dataclass
class DecodeBudgetScheduler:
    """FIFO batcher under a decode budget: admit the longest queue prefix
    whose batched decode cost ``B × D`` stays within ``decode_budget``,
    where D is the power-of-two bucket of the batch's largest
    ``max_new_tokens``. An oversized lone request is still admitted alone
    with its decode capped at the budget (starving it forever would turn a
    budget into a deadlock)."""

    decode_budget: int = 64
    max_batch: int = 4

    def admit(self, queue: Deque[Request]) -> Tuple[List[Request], int]:
        """Pop and return ``(batch, decode_steps)``; empty queue → ([], 0)."""
        if not queue:
            return [], 0
        batch: List[Request] = []
        d = 1
        for req in list(queue):
            cand_d = max(d, _bucket(max(req.max_new_tokens, 1)))
            if batch and (len(batch) + 1 > self.max_batch
                          or (len(batch) + 1) * cand_d > self.decode_budget):
                break
            batch.append(req)
            d = cand_d
            if len(batch) * d >= self.decode_budget:
                break
        for _ in batch:
            queue.popleft()
        return batch, min(d, max(self.decode_budget, 1))


def synthetic_requests(n: int, rate: float = 0.0, prompt_len: int = 32,
                       max_new_tokens: int = 8, vocab_size: int = 256,
                       seed: int = 0) -> List[Request]:
    """A deterministic load: ``n`` requests with exponential inter-arrivals
    at ``rate`` req/s (rate ≤ 0 → everything arrives at t=0)."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab_size, size=(n, prompt_len), dtype=np.int64)
    if rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    else:
        arrivals = np.zeros(n)
    return [Request(rid=i, tokens=toks[i], arrival_s=float(arrivals[i]),
                    max_new_tokens=max_new_tokens) for i in range(n)]


# ---------------------------------------------------------------------------
# one replica
# ---------------------------------------------------------------------------

class ServeReplica:
    """subscribe → apply → serve → resync (DESIGN.md §12). Joins from the
    stream's bootstrap checkpoint (never loading per-client EF state — a
    replica restores only params + opt_state + h), replays the record log,
    and serves through ``Session.serve`` with the subscriber's params
    injected as the serve source. On a gap it resyncs from the newest
    bootstrap past the gap and replays; with no such bootstrap it raises —
    the replica keeps serving its last CONSISTENT model (stale is honest,
    drift is not)."""

    def __init__(self, stream, name: str = "r0", lag: int = 0,
                 bootstrap_step: Optional[int] = None):
        self.tail = transport_lib.make_tail(stream)
        self.name = name
        self.lag = int(lag)
        if bootstrap_step is not None:
            path = self.tail.bootstrap_path(bootstrap_step)
        else:
            # a lagged replica joins at a bootstrap at-or-below its target
            # (head − lag) when one exists, so it starts BEHIND and stays
            # there; fall back to the newest bootstrap otherwise
            head = self.tail.last_step()
            path = None
            if self.lag > 0 and head is not None:
                path = self.tail.latest_bootstrap(
                    upto=max(head - self.lag, 0))
            if path is None:
                path = self.tail.latest_bootstrap()
        if path is None:
            raise stream_lib.StreamError(
                f"stream {stream!r} has no bootstrap checkpoint — a "
                "replica cannot join (params never travel on the wire); "
                "attach the trainer with Session.publish_to first")
        meta = ckpt_lib.read_meta(path)
        if "spec" not in meta:
            raise stream_lib.StreamError(
                f"bootstrap {path} has no embedded RunSpec")
        self.spec = spec_lib.RunSpec.from_dict(meta["spec"])
        self.spec_hash = self.spec.spec_hash()
        self.session = session_lib.Session(self.spec)
        self.optimizer = opt_lib.make(self.spec.optimizer, lr=self.spec.lr)
        self._likes, self.legs = self._like_trees()
        self.sub = self._load_bootstrap(path)
        self.session.set_serve_params(self.sub.params)

    @property
    def log(self):
        """Back-compat alias: the read side of the stream (a StreamTail)."""
        return self.tail

    # -------------------------------------------------------------- loading
    def _like_trees(self) -> Tuple[Dict[str, PyTree], List[Any]]:
        """Shape/dtype templates via eval_shape — a replica restore never
        pays init_params, and never materializes the per-CLIENT EF state
        (``ef_state/clients``): only params, opt_state, and the broadcast
        memory h leave the checkpoint. The transport legs are resolved once
        against the same template and reused everywhere (they decide whether
        the stream carries an h at all)."""
        cfg = self.session.cfg
        params_like = jax.eval_shape(
            lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
        opt_like = jax.eval_shape(self.optimizer.init, params_like)
        legs = stream_lib.resolve_legs(
            params_like,
            schedule=session_lib.make_schedule(self.spec),
            down_carrier=self.spec.downlink_carrier,
            down_compressor=session_lib.make_down_compressor(self.spec))
        likes = {"params": params_like, "opt_state": opt_like}
        if any(leg.carrier is not None for leg in legs):
            likes["h"] = params_like
        return likes, legs

    def _load_bootstrap(self, path: str) -> stream_lib.Subscriber:
        meta = ckpt_lib.read_meta(path)
        stored = meta.get("spec_hash")
        if stored is not None and stored != self.spec_hash:
            raise stream_lib.StreamSpecMismatch(
                f"bootstrap {path} was written by a different RunSpec "
                f"(hash {stored} != {self.spec_hash}); refusing to join a "
                "foreign stream")
        like = {"params": self._likes["params"],
                "opt_state": self._likes["opt_state"]}
        if "h" in self._likes:
            like["ef_state"] = {"h": self._likes["h"]}
        state, meta = ckpt_lib.restore(path, like)
        return stream_lib.Subscriber(
            self.tail, self.spec_hash, self.legs, state["params"],
            state["opt_state"], state.get("ef_state", {}).get("h"),
            int(meta["step"]), self.optimizer)

    # ------------------------------------------------------------------ sync
    @property
    def step(self) -> int:
        return self.sub.step

    @property
    def params(self) -> PyTree:
        return self.sub.params

    def _target(self, upto: Optional[int]) -> Optional[int]:
        last = self.tail.last_step()
        if last is None:
            return None
        target = max(0, last - self.lag)
        return target if upto is None else min(target, int(upto))

    def sync(self, upto: Optional[int] = None) -> int:
        """Apply every record up to (head − lag); on a gap, resync via
        checkpoint + replay. Returns steps advanced. The served params are
        refreshed exactly once per path: the in-order path pushes them here,
        the resync path pushes them itself (it may land on a different
        Subscriber object)."""
        target = self._target(upto)
        if target is None or target <= self.step:
            return 0
        start = self.step
        try:
            if self.sub.sync(upto=target):
                self.session.set_serve_params(self.sub.params)
        except stream_lib.StreamGapError:
            self.resync(target)
        return self.step - start

    def resync(self, target: int) -> int:
        """Gap recovery: reload the newest bootstrap PAST the replica's
        current step and replay forward — the replica re-enters the stream
        bit-identical, never having applied records out of order. Raises
        ``StreamGapError`` when no bootstrap bridges the gap (the replica
        keeps its last consistent, honestly-stale model)."""
        before = self.step
        for b in sorted(self.tail.bootstrap_steps(), reverse=True):
            if b <= self.step or b > target:
                continue
            sub = self._load_bootstrap(self.tail.bootstrap_path(b))
            try:
                sub.sync(upto=target)
            except stream_lib.StreamGapError:
                continue
            self.sub = sub
            self.session.set_serve_params(self.sub.params)
            return self.step - before
        raise stream_lib.StreamGapError(
            f"replica {self.name!r} is at step {before} with a gap before "
            f"step {target} and no bootstrap bridges it; refusing to skip "
            "records (serving stays on the last consistent model)")

    def staleness(self) -> int:
        """Head − replica step, explicitly 0 for an empty log (no records
        published yet means there is nothing to be stale AGAINST — the old
        ``last_step() or 0`` falsy coercion would have made a replica at
        step 5 look −5 stale)."""
        last = self.tail.last_step()
        if last is None:
            return 0
        return max(int(last) - self.step, 0)

    # ----------------------------------------------------------------- serve
    def serve_batch(self, requests: Sequence[Request], prompt_len: int,
                    decode_steps: int,
                    sync_during_decode: bool = False) -> Dict[str, Any]:
        """One batched prefill+decode over ``requests`` at the replica's
        current (synced) params. Prompts are right-padded/truncated to the
        fleet's fixed ``prompt_len`` bucket; the TRUE prompt lengths travel
        with the batch, so the first generated token is read at each row's
        real last prompt position — a prompt containing a genuine token 0 is
        never conflated with padding. With ``sync_during_decode`` the
        replica polls the tail between decode steps and applies any fresh
        records (the remaining decode runs on the updated params); the
        result carries ``mid_applied`` = steps applied mid-decode."""
        assert requests, "serve_batch needs at least one request"
        vocab = self.session.cfg.vocab_size
        toks = np.zeros((len(requests), prompt_len), dtype=np.int64)
        lens = np.zeros((len(requests),), dtype=np.int32)
        for j, req in enumerate(requests):
            row = np.asarray(req.tokens)[:prompt_len] % vocab
            toks[j, :row.size] = row
            lens[j] = max(int(row.size), 1)
        applied = {"n": 0}
        hook = None
        if sync_during_decode:
            def hook(i):
                applied["n"] += self.sync()
        out = self.session.serve(tokens=jax.numpy.asarray(toks),
                                 prompt_lens=jax.numpy.asarray(lens),
                                 decode_steps=decode_steps,
                                 decode_hook=hook)
        out["mid_applied"] = applied["n"]
        return out


# ---------------------------------------------------------------------------
# the fleet
# ---------------------------------------------------------------------------

class Fleet:
    """N replicas subscribed to ONE wire stream at per-replica lags, served
    round-robin under a shared decode-budget scheduler."""

    def __init__(self, stream, n_replicas: int = 2,
                 lags: Optional[Sequence[int]] = None,
                 decode_budget: int = 64, max_batch: int = 4,
                 prompt_len: int = 32,
                 bootstrap_step: Optional[int] = None):
        lags = list(lags) if lags is not None else [0] * n_replicas
        if len(lags) != n_replicas:
            raise ValueError(f"{n_replicas} replicas but {len(lags)} lags")
        self.replicas = [
            ServeReplica(stream, name=f"r{i}", lag=lags[i],
                         bootstrap_step=bootstrap_step)
            for i in range(n_replicas)]
        self.scheduler = DecodeBudgetScheduler(decode_budget=decode_budget,
                                               max_batch=max_batch)
        self.prompt_len = int(prompt_len)

    def sync(self) -> List[int]:
        return [rep.sync() for rep in self.replicas]

    def run(self, requests: Sequence[Request], sync_every: int = 1,
            sync_during_decode: bool = False) -> Dict[str, Any]:
        """Drive the request load through the fleet: arrivals are honored
        against the wall clock, each replica syncs (applies fresh wire
        records) on its OWN batch cadence — every ``sync_every`` batches IT
        serves, counted per replica, so every replica syncs before its first
        batch and no replica can be starved of syncs by the round-robin
        phase (the old global ``batches % sync_every`` check advanced in
        lockstep with the round-robin index, which left whole replicas
        never-synced for ``n_replicas == sync_every``). Each completed
        request records its latency, the staleness (head − replica step) it
        was served at, and ``tokens_generated``; a request whose decode was
        capped by the budget surfaces in the ``short_requests`` /
        ``tokens_short`` summary fields. ``sync_during_decode`` additionally
        applies fresh records BETWEEN decode steps (continuous sync).
        Returns the completed requests plus a QPS/p50/p99 summary."""
        todo = collections.deque(sorted(requests, key=lambda r: r.arrival_s))
        pending: Deque[Request] = collections.deque()
        done: List[Request] = []
        t0 = time.time()
        batches = ri = 0
        served = [0] * len(self.replicas)   # per-replica batch counts
        while todo or pending:
            now = time.time() - t0
            while todo and todo[0].arrival_s <= now:
                pending.append(todo.popleft())
            if not pending:
                time.sleep(min(0.002, max(todo[0].arrival_s - now, 1e-4)))
                continue
            idx = ri % len(self.replicas)
            rep = self.replicas[idx]
            ri += 1
            if sync_every and served[idx] % sync_every == 0:
                rep.sync()
            batch, decode_steps = self.scheduler.admit(pending)
            out = rep.serve_batch(batch, self.prompt_len, decode_steps,
                                  sync_during_decode=sync_during_decode)
            t_done = time.time() - t0
            staleness = rep.staleness()
            for req, row in zip(batch, out["tokens"]):
                req.t_done = t_done
                req.latency_s = t_done - req.arrival_s
                finalize_request(req, row)
                req.replica = rep.name
                req.staleness = staleness
                done.append(req)
            batches += 1
            served[idx] += 1
        return _summary(done, batches)


def _summary(done: List[Request], batches: int, **extra) -> Dict[str, Any]:
    """The shared run-summary schema (in-process Fleet and ProcessFleet):
    QPS/p50/p99, staleness, and the decode-budget shortfall accounting."""
    lat = np.array(sorted(r.latency_s for r in done)) if done \
        else np.zeros(1)
    wall = max((r.t_done for r in done), default=0.0)
    stal = np.array([r.staleness for r in done]) if done else np.zeros(1)
    short = [r for r in done if r.tokens_generated < r.max_new_tokens]
    return {
        "requests": done,
        "batches": batches,
        "qps": len(done) / max(wall, 1e-9),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "staleness_mean": float(stal.mean()),
        "staleness_max": int(stal.max()),
        "short_requests": len(short),
        "tokens_short": int(sum(r.max_new_tokens - r.tokens_generated
                                for r in short)),
        **extra,
    }


# ---------------------------------------------------------------------------
# the multi-process fleet
# ---------------------------------------------------------------------------

# the device nodes through which a process holds a TPU chip: /dev/accel*,
# or one vfio group per chip on v5e and later
ACCELERATOR_NODES = ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*")


def accelerator_nodes() -> List[str]:
    """This host's accelerator device nodes; empty on a CPU-only host."""
    return sorted(p for pat in ACCELERATOR_NODES for p in glob.glob(pat))


def held_nodes(nodes: Sequence[str]) -> List[str]:
    """The nodes among ``nodes`` this process has open — how it holds a
    chip once its JAX backend has started on it."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue                  # closed since the listing
        if target in nodes:
            held.add(target)
    return sorted(held)


def can_spawn_workers(n_workers: int) -> Optional[str]:
    """None when ``n_workers`` replica worker processes can each start
    their JAX backend; else why not. A chip belongs to one process: a
    worker that needs an accelerator this process holds, or one
    accelerator between several workers, fails at start-up or waits on
    the chip forever. Workers inherit this environment, so with
    ``JAX_PLATFORMS=cpu`` every one runs on its own CPU backend, as it
    does on a host with no accelerator."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    nodes = accelerator_nodes()
    if not nodes:
        return None
    held = held_nodes(nodes)
    if held:
        return (f"this process holds the accelerator ({', '.join(held)}) "
                "its replica worker processes would need; serve in-process "
                "(Fleet) or start ProcessFleet from a process that has not "
                "used the accelerator")
    if n_workers > 1:
        return (f"{n_workers} replica worker processes would share this "
                "host's accelerator, which only one process can hold; run "
                "one worker, serve in-process (Fleet), or set "
                "JAX_PLATFORMS=cpu")
    return None


class ProcessFleet:
    """N replica WORKER PROCESSES on one wire stream (DESIGN.md §12): each
    worker is a ``python -m repro.launch.replica_worker`` subprocess running
    its own ``ServeReplica`` over a transport tail, reporting heartbeats to
    this parent. The parent admits request batches under the shared
    decode-budget scheduler and dispatches them to idle workers — batches
    genuinely overlap across processes, which is what "past one process"
    buys. Workers serve with CONTINUOUS sync (records applied between decode
    steps), a crashed worker is restarted and rejoins via checkpoint +
    replay (bit-identical — the §12 anchor invariant across a process
    boundary), and its in-flight batch is requeued at the head of the
    pending queue, so a crash costs latency, never a lost or
    drifted-weights request."""

    def __init__(self, stream, n_workers: int = 2,
                 lags: Optional[Sequence[int]] = None,
                 decode_budget: int = 64, max_batch: int = 4,
                 prompt_len: int = 32,
                 bootstrap_step: Optional[int] = None,
                 heartbeat_s: float = 0.25, hb_timeout_s: float = 120.0,
                 start_timeout_s: float = 300.0):
        from repro.launch import replica_worker as worker_lib

        lags = list(lags) if lags is not None else [0] * n_workers
        if len(lags) != n_workers:
            raise ValueError(f"{n_workers} workers but {len(lags)} lags")
        reason = can_spawn_workers(n_workers)
        if reason is not None:
            raise RuntimeError(reason)
        self.workers = [
            worker_lib.WorkerHandle(
                str(stream), name=f"w{i}", lag=lags[i],
                bootstrap_step=bootstrap_step, prompt_len=prompt_len,
                heartbeat_s=heartbeat_s, start_timeout_s=start_timeout_s)
            for i in range(n_workers)]
        self.scheduler = DecodeBudgetScheduler(decode_budget=decode_budget,
                                               max_batch=max_batch)
        self.prompt_len = int(prompt_len)
        self.hb_timeout_s = float(hb_timeout_s)
        for w in self.workers:
            w.wait_ready()

    # ------------------------------------------------------------- lifecycle
    def __enter__(self) -> "ProcessFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self) -> None:
        for w in self.workers:
            w.stop()

    def sync(self) -> List[int]:
        return [w.call({"cmd": "sync"})["applied"] for w in self.workers]

    def digests(self) -> List[str]:
        return [w.call({"cmd": "digest"})["digest"] for w in self.workers]

    # ------------------------------------------------------------------- run
    def _restart(self, w, inflight: Dict[Any, Any],
                 pending: Deque[Request]) -> None:
        """Restart a dead/hung worker; its in-flight batch (if any) goes
        back to the FRONT of the queue so those requests are served next."""
        entry = inflight.pop(w, None)
        if entry is not None:
            for req in reversed(entry["batch"]):
                pending.appendleft(req)
        w.restart()

    def run(self, requests: Sequence[Request],
            sync_during_decode: bool = True) -> Dict[str, Any]:
        """Drive the load: arrivals against the wall clock, batches admitted
        under the decode budget and dispatched to IDLE workers (true
        multi-process overlap), results collected as they complete. Workers
        sync continuously during decode; staleness is reported by the worker
        at batch completion. Summary schema matches ``Fleet.run`` plus
        ``restarts`` and ``mid_applied``."""
        from repro.launch import replica_worker as worker_lib

        todo = collections.deque(sorted(requests, key=lambda r: r.arrival_s))
        pending: Deque[Request] = collections.deque()
        done: List[Request] = []
        inflight: Dict[Any, Dict[str, Any]] = {}
        t0 = time.time()
        batches = 0
        mid_applied = 0
        while todo or pending or inflight:
            now = time.time() - t0
            while todo and todo[0].arrival_s <= now:
                pending.append(todo.popleft())
            # health: restart dead (or heartbeat-silent) workers, requeueing
            # their in-flight batch
            for w in self.workers:
                dead = not w.alive()
                hung = (w in inflight and self.hb_timeout_s
                        and w.hb_age() > self.hb_timeout_s)
                if dead or hung:
                    self._restart(w, inflight, pending)
            # dispatch to every idle worker while there is work
            for w in self.workers:
                if not pending:
                    break
                if w in inflight or not w.alive():
                    continue
                batch, decode_steps = self.scheduler.admit(pending)
                if not batch:
                    break
                cmd = {"cmd": "serve",
                       "requests": [{"rid": r.rid,
                                     "tokens": np.asarray(r.tokens).tolist(),
                                     "max_new_tokens": r.max_new_tokens}
                                    for r in batch],
                       "decode_steps": decode_steps,
                       "prompt_len": self.prompt_len,
                       "sync_during_decode": sync_during_decode}
                try:
                    mid = w.submit(cmd)
                except worker_lib.WorkerDied:
                    for req in reversed(batch):
                        pending.appendleft(req)
                    continue                   # health pass restarts it
                inflight[w] = {"batch": batch, "id": mid,
                               "decode_steps": decode_steps}
            # collect
            got_reply = False
            for w in list(inflight):
                msg = w.take_reply(timeout=0.0)
                if msg is None:
                    continue
                entry = inflight[w]
                if msg.get("id") != entry["id"] or not msg.get("ok"):
                    # a failed serve (or stale reply) — requeue and restart
                    self._restart(w, inflight, pending)
                    continue
                inflight.pop(w)
                got_reply = True
                t_done = time.time() - t0
                head, step = msg.get("head"), msg.get("step", 0)
                staleness = 0 if head is None else max(int(head) - step, 0)
                mid_applied += int(msg.get("mid_applied", 0))
                by_rid = {r.rid: r for r in entry["batch"]}
                for rid, toks, ngen in zip(msg["rids"], msg["tokens"],
                                           msg["tokens_generated"]):
                    req = by_rid[rid]
                    req.t_done = t_done
                    req.latency_s = t_done - req.arrival_s
                    req.tokens_out = np.asarray(toks, dtype=np.int64)
                    req.tokens_generated = int(ngen)
                    req.replica = w.name
                    req.staleness = staleness
                    done.append(req)
                batches += 1
            if not got_reply:
                time.sleep(0.002)
        return _summary(done, batches,
                        restarts=sum(w.restarts for w in self.workers),
                        mid_applied=mid_applied,
                        workers=[w.name for w in self.workers])
