"""Two-tier hierarchical EF aggregation (DESIGN.md §13): clients → pod
aggregator → global server, each hop with its own carrier/compressor.

The flat round aggregates every client message straight into the server
update, so the expensive cross-pod hop pays exactly the same wire cost as
the cheap intra-pod hop. With ``Hops(pods=P, cross_carrier=..., ...)`` the
round becomes two hops:

  1. INTRA hop: the clients of pod p aggregate their messages over the fast
     intra-pod links exactly as today (same carriers, same plans), producing
     the pod mean u_p instead of the global mean.
  2. CROSS hop: each pod aggregator keeps its OWN EF memory — a target
     ``t_p`` (what the pod wants the server to know) and a broadcast state
     ``b_p`` (what the server actually holds of this pod) — and ships only
     the compressed innovation C_cross(t_p' − b_p) across the slow inter-pod
     links; the server integrates the decode. This is the uplink twin of the
     §8 downlink memory: ``b_p' = b_p + decode(C_cross(t_p' − b_p))`` via the
     SAME ``ef_lib.downlink_sync`` leg, so compounding compression error is
     error-fed at both levels (EF21 composes across heterogeneous links —
     "EF21 with Bells & Whistles", PAPERS.md).

Pod target update and server update reuse the method's server semantics:

  delta mode:     t_p' = t_p + u_p        g' = g + mean_p(b_p' − b_p)
  absolute mode:  t_p' = u_p              g' = mean_p(b_p')

Both pod memories initialize to zeros; in delta mode the server increment
mean_p(b_p' − b_p) is exact regardless of how g⁰ itself was initialized.

A TRIVIAL cross hop (dense carrier + identity compressor) makes the pod
aggregator transparent: ``b_p' = t_p'`` bit-exactly, the round executes the
legacy flat aggregation ops verbatim (the flat-equivalence anchor
tests/test_hierarchy.py pins bit-identity), and the pod memories degenerate
to tracking the global innovation sum. ``pods=1`` (or hops=None) is a pure
no-op: no pod state exists and the emitted jaxpr is the legacy one.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import carriers as carrier_lib
from repro.core import compressors as comp_lib
from repro.core import ef as ef_lib

# rng fold for the cross-pod hop, derived from the ROUND rng before the
# per-client fold (exactly like carriers.DOWNLINK_FOLD = 1 << 20 for the
# broadcast leg) and then per-pod: fold_in(fold_in(rng, CROSS_FOLD), pod).
# Distinct from DOWNLINK_FOLD so a bidirectional hierarchical round never
# reuses a stream between the cross hop and the broadcast.
CROSS_FOLD = 1 << 21


@dataclasses.dataclass(frozen=True)
class Hops:
    """The two-hop topology knob: how many pod aggregators, and the
    cross-pod carrier/compressor. Frozen → hashable, usable as a jit
    static (SimConfig carries one). The intra hop has no fields here — it
    runs the round's existing carrier/compressor/schedule unchanged, just
    aggregated over the intra-pod axes only."""

    pods: int = 1
    cross_carrier: str = "dense"
    cross_compressor: Optional[comp_lib.Compressor] = None

    def cross_comp(self) -> comp_lib.Compressor:
        return (self.cross_compressor if self.cross_compressor is not None
                else comp_lib.Identity())

    @property
    def trivial_cross(self) -> bool:
        """True when the cross hop ships the exact pod target (dense carrier,
        identity compressor) — the flat-equivalence regime."""
        return (carrier_lib.make(self.cross_carrier).name == "dense"
                and isinstance(self.cross_comp(), comp_lib.Identity))


def effective(hops: Optional[Hops]) -> Optional[Hops]:
    """Normalize to None when the topology is flat (pods <= 1): callers gate
    ALL hierarchical machinery on ``effective(hops) is not None``, so a
    pods=1 config creates no pod state and traces the legacy jaxpr."""
    if hops is None or hops.pods <= 1:
        return None
    return hops


def check_pods(hops: Hops, n: int) -> None:
    if n % hops.pods != 0:
        raise ValueError(
            f"hops.pods={hops.pods} must divide the client count {n}")


def pod_init(params_like) -> dict:
    """Per-pod EF memory: target t (what the pod wants upstream) and
    broadcast state b (what the server holds of this pod). Both zeros —
    the server increment mean_p(b' − b) is exact under any g⁰ init."""
    return {"t": ef_lib.tree_zeros_like(params_like),
            "b": ef_lib.tree_zeros_like(params_like)}


def pod_target(method, t, u):
    """Fold the pod's intra-hop mean u into its target: the method's own
    server semantics (delta accumulates, absolute replaces)."""
    return ef_lib.server_step(method, t, u)


def pod_message(method, b, b_new):
    """One pod's contribution to the server update: the cross-hop decode
    increment (delta mode) or the synced absolute target. The server then
    runs ``server_step(method, g, mean_p(pod_message))``."""
    if method.mode == "delta":
        return ef_lib.tree_sub(b_new, b)
    return b_new


def cross_sync(hops: Hops, schedule, t_new, b, rng):
    """The cross hop for ONE pod: b' = b + decode(C_cross(t' − b)), reusing
    the §8 downlink leg (same encode/decode/rng-per-leaf discipline). With a
    per-group schedule the group's cross fields are authoritative
    (schedule.cross_round_grouped); otherwise the uniform Hops knobs run."""
    if schedule is not None:
        from repro.core import schedule as sched_lib
        return sched_lib.cross_round_grouped(schedule, t_new, b, rng)
    car = carrier_lib.make(hops.cross_carrier)
    return ef_lib.downlink_sync(car, hops.cross_comp(), t_new, b, rng=rng)[1]


def cross_is_trivial(hops: Hops, schedule) -> bool:
    """Flat-equivalence predicate for the whole cross hop: with a schedule,
    EVERY group's cross must be trivial."""
    if schedule is None:
        return hops.trivial_cross
    return all(g.trivial_cross for g in schedule.groups)


def pod_mean(tree, pods: int):
    """Per-pod means of a clients-leading-axis tree: (n, ...) → (pods, ...)
    with pod-major contiguous blocks (client i belongs to pod i // (n/pods)
    — the same pod-major order the sharded runtime's client_index
    composes, so both runtimes agree on who is in which pod)."""
    def one(leaf):
        m = leaf.shape[0] // pods
        return leaf.reshape(pods, m, *leaf.shape[1:]).mean(1)
    return jax.tree_util.tree_map(one, tree)


def round_pods_batched(hops: Hops, schedule, method, u_pods, pods_st,
                       g_server, rng):
    """The full pod tier for the vmap runtimes: per-pod target update, the
    cross hop (per-pod rng = fold_in(fold_in(rng, CROSS_FOLD), pod) — the
    same stream the sharded runtime folds), and the server integration.

    ``u_pods``/``pods_st`` carry pods on a leading axis. Returns
    ``(new_pods_st, new_server)``."""
    pods = hops.pods
    r_cross = None if rng is None else jax.random.fold_in(rng, CROSS_FOLD)
    t_out, b_out, msgs = [], [], []
    for p in range(pods):
        take = lambda tr: jax.tree_util.tree_map(lambda l: l[p], tr)
        t_p, b_p = take(pods_st["t"]), take(pods_st["b"])
        t_new = pod_target(method, t_p, take(u_pods))
        r_p = None if r_cross is None else jax.random.fold_in(r_cross, p)
        b_new = cross_sync(hops, schedule, t_new, b_p, r_p)
        t_out.append(t_new)
        b_out.append(b_new)
        msgs.append(pod_message(method, b_p, b_new))
    stack = lambda ts: jax.tree_util.tree_map(
        lambda *ls: jnp.stack(ls), *ts)
    msg_mean = jax.tree_util.tree_map(
        lambda *ls: sum(ls[1:], ls[0]) / pods, *msgs)
    new_server = ef_lib.server_step(method, g_server, msg_mean)
    return {"t": stack(t_out), "b": stack(b_out)}, new_server


def trivial_bookkeeping(method, pods_st, msg_mean):
    """Pod-memory update under a TRIVIAL cross hop: the aggregator is
    transparent (b' = t'), the server consumed the legacy GLOBAL mean
    bit-exactly, and the pod memories track that same global innovation —
    one rule shared by both runtimes so they agree bit-for-bit.
    ``msg_mean`` broadcasts against the pod state, which carries a leading
    pods axis in the vmap runtimes and none inside shard_map."""
    def up(t_leaf, m_leaf):
        m = jnp.broadcast_to(m_leaf, t_leaf.shape)
        return t_leaf + m if method.mode == "delta" else m
    t_new = jax.tree_util.tree_map(up, pods_st["t"], msg_mean)
    return {"t": t_new, "b": t_new}


def wire_words_cross(hops: Hops, schedule, method, tree_or_d) -> float:
    """Cross-pod words per ROUND: each pod ships one compressed innovation,
    so the per-message count (the §8 ``downlink_words`` twin — the cross
    wire is one message, no aggregation) × pods."""
    if schedule is not None:
        from repro.core import schedule as sched_lib
        _, total = sched_lib.wire_words_tree(schedule, method, tree_or_d,
                                             direction="cross")
        return total * hops.pods
    car = carrier_lib.make(hops.cross_carrier)
    d = tree_or_d if isinstance(tree_or_d, (int, float)) else int(
        ef_lib.tree_dim(tree_or_d))
    return carrier_lib.downlink_words(car, hops.cross_comp(), int(d)) \
        * hops.pods
