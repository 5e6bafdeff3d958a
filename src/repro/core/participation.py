"""Partial participation + asynchronous EF rounds (DESIGN.md §11).

"EF21 with Bells & Whistles" (Fatkhullin, Sokolov, Richtárik 2021) extends
EF21 to rounds where only a sampled cohort S ⊆ [n] uploads: sampled clients
run their usual update gᵢ ← gᵢ + cᵢ, NON-sampled clients keep gᵢ (and any
momentum buffer vᵢ) frozen, and the server folds g ← g + (1/n)·Σ_{i∈S} cᵢ —
divide by n, not |S|, so the invariant g_server = meanᵢ gᵢ survives every
round. The source paper's EF21-SGDM momentum buffer is exactly the per-client
state that must stay consistent across skipped rounds, which is why the
freeze is a hard tree-level ``where`` and not a "small update".

Both synchronous runtimes (``ef_round``, which core/simulate.py drives, and
``ef_round_sharded``, in core/distributed.py) implement the rule by MASKING:
the sampled cohort is a seeded 0/1 mask over clients (:func:`cohort_mask` —
a pure function of (seed, round), so resume replays identical cohorts),
non-sampled wire contributions are zeroed BEFORE the aggregation collective
(C(0) = 0 exactly for every deterministic wire compressor, so a zero-masked
delta produces an exactly-zero decode), and the whole per-client state tree
is frozen afterwards with :func:`freeze_tree`.
A fraction-1.0 cohort multiplies by 1.0 and ``where(True, …)`` everywhere —
IEEE-exact — so the masked path is BIT-identical to full participation
(tests/test_participation.py pins this on both runtimes and the simulator).

Absolute-mode methods (EF14, SGDM, …) have no server increment to divide by
n; their server state is the cohort mean (1/|S|)·Σ_{i∈S} msgᵢ, i.e. the
masked mean rescaled by n/|S| (:func:`rescale_message`).

``mode='async'`` never runs on the synchronous runtimes (they are barrier
loops); :func:`run_async` is the event-driven simulator — an ADPSGD-style
client loop with per-client compute-time models (uniform / heavy-tail /
dropout), per-arrival server folds c/n, staleness tracking with an optional
cap, and honest wall-clock-vs-round accounting against the synchronous
barrier baseline (tests/test_async_scenarios.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

PyTree = Any

PART_MODES = ("full", "sampled", "async")


@dataclasses.dataclass(frozen=True)
class Participation:
    """Who uploads each round. Frozen/hashable → usable inside the jit-static
    EFConfig/SimConfig. ``fraction``/``seed`` only matter for mode='sampled'
    (and as defaults for the async simulator's cohort bookkeeping)."""

    mode: str = "full"          # 'full' | 'sampled' | 'async'
    fraction: float = 1.0       # sampled cohort size = max(1, round(f·n))
    seed: int = 0               # cohort stream seed (independent of data rng)

    def __post_init__(self):
        if self.mode not in PART_MODES:
            raise ValueError(f"participation mode {self.mode!r} not in "
                             f"{list(PART_MODES)}")
        if not 0.0 < self.fraction <= 1.0:
            raise ValueError(f"participation fraction must be in (0, 1], "
                             f"got {self.fraction}")

    @property
    def is_sampling(self) -> bool:
        """True when the synchronous runtimes must run the masked-cohort
        path (mode='sampled'; a full mode or an absent Participation runs
        the legacy path untouched)."""
        return self.mode == "sampled"

    def cohort_size(self, n: int) -> int:
        """|S| = max(1, round(fraction·n)) — mirrored jax-free in
        launch/spec.py::participation_preview (sync-tested)."""
        if self.mode == "full":
            return n
        return max(1, int(round(self.fraction * n)))


def cohort_mask(part: Participation, n: int, step) -> "Any":
    """The round's 0/1 client mask, shape (n,) f32: a seeded permutation of
    [n] keeps the first ``cohort_size`` entries. Pure in (seed, step) — the
    SAME (seed, step) yields the same cohort on every runtime and across a
    kill-and-resume — and jit-traceable in ``step`` (cohort_size is static).
    fraction=1.0 returns all-ones (perm[:n] covers [n])."""
    import jax
    import jax.numpy as jnp
    m = part.cohort_size(n)
    key = jax.random.fold_in(jax.random.PRNGKey(part.seed), step)
    perm = jax.random.permutation(key, n)
    return jnp.zeros((n,), jnp.float32).at[perm[:m]].set(1.0)


def cohort_mask_np(part: Participation, n: int, step: int) -> np.ndarray:
    """``cohort_mask`` materialized to numpy (property tests / accounting)."""
    import jax
    return np.asarray(jax.device_get(cohort_mask(part, n, step)))


# ---------------------------------------------------------------------------
# masking / freezing primitives the runtimes share
# ---------------------------------------------------------------------------

def apply_mask(mask, tree: PyTree) -> PyTree:
    """Zero the non-cohort entries of a per-client tree. ``mask`` is either
    the (n,) round mask (batched vmap layouts — broadcast over the leading
    client axis) or this device's scalar entry (shard_map layouts). The
    multiply is cast to each leaf's dtype, so ×1.0 / ×0.0 stay IEEE-exact in
    f32 and bf16 alike — the masked path at fraction=1.0 is bitwise the
    unmasked one."""
    import jax

    def one(x):
        m = mask.astype(x.dtype)
        if m.ndim == 1:
            m = m.reshape((m.shape[0],) + (1,) * (x.ndim - 1))
        return x * m
    return jax.tree_util.tree_map(one, tree)


def freeze_tree(mask, new: PyTree, old: PyTree) -> PyTree:
    """The frozen-client invariant: non-sampled clients keep their ENTIRE
    EF state (gᵢ, momentum, …) — ``where(mask, new, old)`` leaf-wise, never
    arithmetic (a += 0 could still flip -0.0). Same mask layouts as
    ``apply_mask``."""
    import jax
    import jax.numpy as jnp

    def one(nw, od):
        m = mask
        if m.ndim == 1:
            m = m.reshape((m.shape[0],) + (1,) * (nw.ndim - 1))
        return jnp.where(m.astype(bool), nw, od)
    return jax.tree_util.tree_map(one, new, old)


def rescale_message(method, msg_mean: PyTree, n: int, m: int) -> PyTree:
    """Masked aggregates come back as (1/n)·Σ_{i∈S}. For delta-mode methods
    that IS the Bells & Whistles server increment — untouched. Absolute-mode
    methods average over the cohort, so the masked mean rescales by n/m
    (×1.0 exact when m = n)."""
    import jax
    if method.mode != "absolute":
        return msg_mean
    scale = float(n) / float(m)
    return jax.tree_util.tree_map(lambda x: x * scale, msg_mean)


# ---------------------------------------------------------------------------
# event-driven asynchronous rounds (mode='async')
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArrivalModel:
    """Per-client compute-time model for the async event loop.

    'uniform'     τ ~ U[0.5, 1.5]·mean — homogeneous fleet, the sanity model
    'heavy_tail'  τ ~ Pareto(alpha) scaled to E[τ] = mean — stragglers: the
                  per-round max (what a synchronous barrier pays) is far
                  above the mean an async server pays
    'dropout'     uniform times, but each compute is LOST with prob
                  drop_prob (client restarts) — the liveness scenario
    """

    kind: str = "uniform"       # 'uniform' | 'heavy_tail' | 'dropout'
    mean: float = 1.0
    alpha: float = 1.3          # Pareto tail index (heavy_tail; > 1)
    drop_prob: float = 0.2      # P[one compute is lost] (dropout)

    def __post_init__(self):
        if self.kind not in ("uniform", "heavy_tail", "dropout"):
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        if self.kind == "heavy_tail" and self.alpha <= 1.0:
            raise ValueError("heavy_tail needs alpha > 1 (finite mean), "
                             f"got {self.alpha}")
        if not 0.0 <= self.drop_prob < 1.0:
            raise ValueError("drop_prob must be in [0, 1) — a client that "
                             f"always drops deadlocks, got {self.drop_prob}")

    def sample(self, rs: np.random.RandomState, size: int) -> np.ndarray:
        if self.kind == "heavy_tail":
            # Pareto(alpha) with minimum xm has mean xm·alpha/(alpha-1);
            # pick xm so E[τ] = mean
            xm = self.mean * (self.alpha - 1.0) / self.alpha
            return xm * (1.0 + rs.pareto(self.alpha, size=size))
        return self.mean * rs.uniform(0.5, 1.5, size=size)

    def dropped(self, rs: np.random.RandomState, size: int) -> np.ndarray:
        if self.kind != "dropout":
            return np.zeros(size, dtype=bool)
        return rs.uniform(size=size) < self.drop_prob


def sync_barrier_wallclock(arrival: ArrivalModel, n: int, rounds: int,
                           seed: int = 0) -> float:
    """What a synchronous barrier pays under the same compute-time model:
    each round waits for the SLOWEST client (dropped computes retry within
    the round — the barrier cannot proceed without every upload)."""
    rs = np.random.RandomState(seed)
    total = 0.0
    for _ in range(rounds):
        t = arrival.sample(rs, n)
        pending = arrival.dropped(rs, n)
        while pending.any():                 # resample lost computes
            k = int(pending.sum())
            t[pending] += arrival.sample(rs, k)
            pending[pending] = arrival.dropped(rs, k)
        total += float(t.max())
    return total


def run_async(problem, method, n: int, gamma: float, rounds: int,
              arrival: ArrivalModel = ArrivalModel(),
              batch_size: int = 1, b_init: int = 1, eta=None,
              staleness_cap: Optional[int] = None, seed: int = 0) -> Dict:
    """Event-driven asynchronous EF rounds, ADPSGD-style client loop.

    Every client perpetually (fetch x → compute a stochastic gradient,
    taking τ ~ ``arrival`` → upload). The server processes uploads in
    arrival-time order: each accepted upload folds the client's compressed
    innovation as g ← g + c/n (the Bells & Whistles rule with a singleton
    cohort — all other clients are implicitly frozen because only the
    uploader's state advances) and immediately takes a model step
    x ← x − γ·g. One ROUND = n accepted uploads, so round counts compare
    1:1 against the synchronous runtimes; wall-clock is the event time of
    the last accepted upload.

    Staleness of an upload = server model version now − version the client
    fetched. With ``staleness_cap`` set, an upload older than the cap is
    DISCARDED (the client's state never advanced — it simply refetches and
    recomputes), bounding the stale-wire age histogram by construction.
    Dropout ('dropout' arrivals) loses computes but never deadlocks: a lost
    compute reschedules immediately and drop_prob < 1 guarantees progress.

    Delta-mode methods only (the EF21 family — the per-arrival fold IS the
    partial-participation rule; absolute-mode methods have no incremental
    server memory to fold into)."""
    import jax
    from repro.core import ef as ef_lib

    if method.mode == "absolute":
        raise ValueError(
            f"run_async supports delta-mode (EF21-family) methods only; "
            f"{method.name!r} is absolute-mode — its server state is a "
            "cohort mean, which has no per-arrival incremental fold")

    rs = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)

    x = problem.init_x()
    # Alg 1 line 2 init handshake (synchronous, like the sync runtimes)
    g0 = []
    for i in range(n):
        key, k = jax.random.split(key)
        gs = [problem.stoch_grad(x, i, kk, batch_size)
              for kk in jax.random.split(k, b_init)]
        g0.append(jax.tree_util.tree_map(
            lambda *g: sum(g[1:], g[0]) / len(g), *gs))
    states = [method.init(x, init_grads=g) for g in g0]
    g_server = ef_lib.server_init(
        method, x, jax.tree_util.tree_map(lambda *g: sum(g[1:], g[0]) / n,
                                          *g0))

    def start_compute(i, now):
        """Client i fetches the current model and schedules its upload."""
        key_i = jax.random.fold_in(key, counter[0])
        counter[0] += 1
        tau = float(arrival.sample(rs, 1)[0])
        clients[i] = {
            "arrival": now + tau,
            "version": version[0],
            "x": x_now[0],
            "rng": key_i,
            "lost": bool(arrival.dropped(rs, 1)[0]),
        }

    counter = [0]
    version = [0]                # server model version (accepted uploads)
    x_now = [x]
    clients: Dict[int, Dict] = {}
    for i in range(n):
        start_compute(i, 0.0)

    target = n * rounds
    applied = dropped = discarded = 0
    wall_clock = 0.0
    ages: list = []
    gns_round = []

    while applied < target:
        i = min(clients, key=lambda c: clients[c]["arrival"])
        ev = clients[i]
        now = ev["arrival"]
        if ev["lost"]:                      # dropout: compute never arrived
            dropped += 1
            start_compute(i, now)
            continue
        age = version[0] - ev["version"]
        if staleness_cap is not None and age > staleness_cap:
            discarded += 1                   # too stale: refetch, recompute
            start_compute(i, now)
            continue
        # accepted upload: the client's EF update against the model it saw
        grads = problem.stoch_grad(ev["x"], i, ev["rng"], batch_size)
        msg, states[i] = method.update(grads, states[i],
                                       jax.random.fold_in(ev["rng"], 1),
                                       eta=eta)
        g_server = ef_lib.tree_add(
            g_server, jax.tree_util.tree_map(lambda c: c / n, msg))
        x_now[0] = jax.tree_util.tree_map(lambda p, g: p - gamma * g,
                                          x_now[0], g_server)
        version[0] += 1
        applied += 1
        wall_clock = now
        ages.append(age)
        if applied % n == 0:
            gns_round.append(float(ef_lib.tree_norm_sq(
                problem.full_grad(x_now[0]))))
        start_compute(i, now)

    ages_arr = np.asarray(ages, dtype=np.int64)
    hist = np.bincount(ages_arr) if ages_arr.size else np.zeros(1, np.int64)
    return {
        "wall_clock": wall_clock,
        "rounds": rounds,
        "arrivals_applied": applied,
        "arrivals_dropped": dropped,
        "arrivals_discarded": discarded,
        "stale_age_hist": hist,
        "max_staleness": int(ages_arr.max()) if ages_arr.size else 0,
        "mean_staleness": float(ages_arr.mean()) if ages_arr.size else 0.0,
        "grad_norm_sq_per_round": np.asarray(gns_round),
        "grad_norm_sq": gns_round[-1] if gns_round else float("nan"),
        "loss": float(problem.loss(x_now[0])),
        "x_final": jax.device_get(x_now[0]),
        "sync_wall_clock": sync_barrier_wallclock(arrival, n, rounds,
                                                  seed=seed + 1),
    }
