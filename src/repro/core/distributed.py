"""Distributed EF gradient synchronization on a (pod, data, model) mesh.

Mapping (DESIGN.md §3): the paper's n clients are the data-parallel groups of the
mesh. Per-client EF state carries a leading ``dp`` axis sharded over the data mesh
axes, so client i's (vᵢ, gᵢ, …) live exactly on client i's chips. Per-client
gradients are obtained *inside* the jitted step by reshaping the global batch to
(dp, B/dp, …) and vmapping the loss gradient — no collective is needed to keep them
per-client, because batch and state shardings agree on the leading axis.

Aggregation carriers (core/carriers.py, DESIGN.md §6): both runtimes here
dispatch the wire format of meanᵢ(cᵢ) through :mod:`repro.core.carriers` —

  'dense'  — paper-faithful: a d-word all-reduce over the data axes (what the
             paper's own simulations do; no wire savings — the §Perf baseline).
  'sparse' — fixed-(values, block-local indices) wire for the TopK family: an
             all-gather of the small arrays over the data axes plus a local
             scatter-add. Collective bytes drop by ~d/(2·dp·K) on the
             gradient-sync path. Identical math (validated against 'dense').
  'fused'  — dense wire, but the whole EF21-SGD(M) client update runs as ONE
             Pallas HBM pass (kernels/ef_update.py) instead of the unfused
             pre_compress → C(·) → post_compress chain.
  'quant8' / 'quant4'
           — block-quantized wire (per-block absmax scale + int8 or packed
             uint4 mantissas, kernels/quantize.py): sparse-block payloads
             all-gather the still-quantized (mantissas, scales, indices)
             arrays; dense payloads dequantize locally before the psum (an
             int8 all-reduce across differing scales is not associative).
             EF re-sends the quantization error — local_c is the wire decode.
  'fused_quant8' / 'fused_quant4'
           — the one-launch round: the whole client chain (EF update +
             Block-TopK + quantize + EF-invariant integration) runs as ONE
             Pallas mega-kernel (kernels/fused_round.py) and the quantized
             block-dense payload is what aggregates (dequantize, then pmean).

Bidirectional compression (DESIGN.md §8): ``EFConfig.down_carrier`` /
``down_compressor`` add a DOWNLINK leg to the round — the server keeps an
EF21 broadcast memory h (``ef_state['h']``), broadcasts the carrier wire of
C_down(g_server − h), and the model steps with the decode-integrated
hᵗ⁺¹ = hᵗ + decode(wire) on server and clients alike, so both provably hold
identical models without ever shipping dense f32 down. The default
(down_carrier='dense', no compressor) runs NO downlink machinery and is
bit-identical to the unidirectional runtime.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import carriers as carrier_lib
from repro.core import compressors as comp_lib
from repro.core import ef as ef_lib
from repro.core import hierarchy as hier_lib
from repro.core import participation as part_lib
from repro.core import schedule as sched_lib
from repro.telemetry import (DOWNLINK, EF_AGGREGATE, EF_CLIENT, MODEL,
                             OPTIMIZER, collective_round)

PyTree = Any

# re-exported for callers that only import the runtime module
DOWNLINK_FOLD = carrier_lib.DOWNLINK_FOLD
CROSS_FOLD = hier_lib.CROSS_FOLD


@dataclasses.dataclass(frozen=True)
class EFConfig:
    method: ef_lib.Method
    carrier: str = "dense"     # any core/carriers.py REGISTRY name
    data_axes: Tuple[str, ...] = ("data",)  # mesh axes forming the client dim
    b_init_scale: bool = True              # Alg 1 line 2: init v⁰=g⁰ to first grads
    # downlink (server → client broadcast) leg, DESIGN.md §8: 'dense' with no
    # compressor means NO downlink machinery at all — the broadcast is the
    # implicit dense g_server, bit-identical to the unidirectional runtime
    down_carrier: str = "dense"
    down_compressor: Optional[comp_lib.Compressor] = None
    # per-parameter-group compression (DESIGN.md §9): when set, BOTH runtimes
    # dispatch every leg (uplink wire, aggregation, downlink, state init)
    # through the grouped engine in core/schedule.py and the single-knob
    # fields above (carrier / down_*) are ignored — each group carries its
    # own. None runs the legacy single-compressor path unchanged; a uniform
    # one-group schedule is bit-identical to it (tests/test_schedule.py).
    schedule: Optional[sched_lib.CompressionSchedule] = None
    # comm/compute overlap (DESIGN.md §10): gather-wire aggregations on the
    # shard_map runtime transport their all-gathers as a ppermute ring and
    # decode each chunk while the next is in flight. Bit-identical to the
    # blocking anchor (the ring reproduces all_gather's axis order exactly);
    # a no-op for all-reduce wires and for the vmap runtimes (no collectives)
    overlap: bool = False
    # partial participation (DESIGN.md §11): mode='sampled' runs the masked
    # cohort path — a seeded per-round mask zeroes non-sampled wires before
    # the aggregation collective and freezes their whole EF state tree (the
    # "EF21 with Bells & Whistles" rule). None (or mode='full') runs the
    # legacy full-cohort path untouched; a sampled fraction=1.0 cohort is
    # bit-identical to it (tests/test_participation.py). mode='async' never
    # runs here — core/participation.py::run_async is the async simulator.
    participation: Optional[part_lib.Participation] = None
    # two-tier hierarchical aggregation (DESIGN.md §13): clients → pod
    # aggregator → global server. The intra hop runs this config's existing
    # carrier/schedule over the intra-pod axes only; each pod keeps its own
    # EF memory (ef_state['pods'] = {t, b}) and ships the compressed
    # cross-pod innovation via hops.cross_carrier/cross_compressor. None or
    # pods=1 runs ZERO hierarchical machinery (bit-identical legacy jaxpr).
    hops: Optional[hier_lib.Hops] = None

    @property
    def effective_hops(self) -> Optional[hier_lib.Hops]:
        return hier_lib.effective(self.hops)

    @property
    def has_downlink(self) -> bool:
        if self.schedule is not None:
            return self.schedule.has_downlink
        return self.down_carrier != "dense" or self.down_compressor is not None

    def down_comp(self) -> comp_lib.Compressor:
        return self.down_compressor if self.down_compressor is not None \
            else comp_lib.Identity()


# ---------------------------------------------------------------------------
# per-client gradients
# ---------------------------------------------------------------------------

def _client_split(dp: int) -> Optional[Tuple[str, ...]]:
    """The ambient mesh's client axes (``pod``, ``data``) when they split
    ``dp`` clients evenly, else None: the axes that carry the clients'
    dimension through their forward and backward, so that a kernel run
    under ``shard_map`` there gets its own clients' rows."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes if axes and dp % n == 0 else None


def per_client_value_and_grad(loss_fn: Callable, params: PyTree, batch: PyTree,
                              dp: int) -> Tuple[jax.Array, PyTree, PyTree]:
    """loss_fn(params, sub_batch) -> (loss, aux). Returns (mean loss, aux,
    per-client grads with a leading dp axis). Under a mesh, the clients'
    dimension is split over its client axes (:func:`_client_split`)."""
    def reshape(leaf):
        b = leaf.shape[0]
        assert b % dp == 0, f"global batch {b} not divisible by dp={dp}"
        return leaf.reshape(dp, b // dp, *leaf.shape[1:])

    batch_g = jax.tree_util.tree_map(reshape, batch)

    def one(b):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
        return loss, aux, grads

    losses, auxs, grads = jax.vmap(one, spmd_axis_name=_client_split(dp))(
        batch_g)
    aux = jax.tree_util.tree_map(lambda a: a.mean(0), auxs)
    return losses.mean(), aux, grads


# ---------------------------------------------------------------------------
# EF state init
# ---------------------------------------------------------------------------

def init_ef_state(efc: EFConfig, params: PyTree, dp: int,
                  init_grads: Optional[PyTree] = None) -> Dict:
    """init_grads: optional per-client grads (dp leading) for Alg 1 line 2."""
    method = efc.method
    if efc.schedule is not None:
        # per-group init (per-group EF-state dtypes), merged onto the full
        # treedef — bit-identical to method.init for a uniform schedule
        init_one = lambda p, g=None: sched_lib.init_state_grouped(  # noqa: E731
            efc.schedule, method, p, init_grads=g)
    else:
        init_one = method.init
    if init_grads is None:
        clients = jax.vmap(lambda _: init_one(params))(jnp.arange(dp))
        server = ef_lib.server_init(method, params)
    else:
        clients = jax.vmap(lambda g: init_one(params, g))(init_grads)
        server = ef_lib.server_init(
            method, params,
            jax.tree_util.tree_map(lambda g: g.mean(0), init_grads))
    state = {"clients": clients, "server": server}
    if efc.has_downlink:
        # the broadcast memory h⁰ = g⁰ rides along as a state sibling; the
        # unidirectional state tree stays byte-for-byte what it always was
        state["h"] = ef_lib.downlink_init(server)
    hops = efc.effective_hops
    if hops is not None:
        # per-pod EF memory on a leading pods axis (sharded over the 'pod'
        # mesh axis on the production path) — a flat config's state tree is
        # untouched, exactly like the downlink's 'h' sibling
        hier_lib.check_pods(hops, dp)
        state["pods"] = jax.vmap(lambda _: hier_lib.pod_init(params))(
            jnp.arange(hops.pods))
    return state


# ---------------------------------------------------------------------------
# one synchronization round
# ---------------------------------------------------------------------------

def _participation_mask(efc: EFConfig, n: int, step):
    """The round's cohort mask for a sampled-participation config, or None
    on the legacy full path. Hard-errors on async (a barrier runtime cannot
    honor arrival order) and on a missing step (the cohort is a pure
    function of (seed, round) so resume replays it)."""
    part = efc.participation
    if part is None or part.mode == "full":
        return None
    if part.mode == "async":
        raise ValueError(
            "participation mode 'async' does not run on the synchronous "
            "runtimes (every round is a barrier); drive the event-driven "
            "simulator instead: repro.core.participation.run_async")
    if step is None:
        raise ValueError(
            "sampled participation derives the round cohort from the step "
            "index; pass step= into ef_round / ef_round_sharded")
    return part_lib.cohort_mask(part, n, step)


def check_composition(efc: EFConfig, eta, n: int, mask) -> None:
    """The round's composition rule, checked once before its plan switch:
    a sampled cohort (``mask``) cannot run the fused wire, and neither a
    sampled cohort nor the fused wire can run under a hierarchy. The fused
    wire is the plan the carrier (or any schedule group's) executes at
    ``eta``. ``launch/build.py`` refuses the same configs by carrier name."""
    if efc.schedule is None:
        plans = {carrier_lib.make(efc.carrier).plan(efc.method, eta)}
    else:
        plans = {carrier_lib.make(g.carrier).plan(
            sched_lib.group_method(efc.method, g), eta)
            for g in efc.schedule.groups}
    fused_wire = "fused_wire" in plans
    if mask is not None and fused_wire:
        raise ValueError(
            "sampled participation cannot run the fused_wire plan: the "
            "mega-kernel aggregates all clients inside, leaving no "
            "per-client wire to mask")
    hops = efc.effective_hops
    if hops is None:
        return
    hier_lib.check_pods(hops, n)
    if mask is not None:
        raise ValueError(
            "sampled participation does not compose with hierarchical "
            "aggregation: a per-round cohort has no stable pod membership")
    if fused_wire:
        raise ValueError(
            "fused_wire carriers aggregate all clients inside the "
            "mega-kernel — there is no per-pod message to re-aggregate")


def downlink_leg(efc: EFConfig, g_server: PyTree, h: PyTree,
                 rng: Optional[jax.Array], memory: bool = True
                 ) -> Tuple[PyTree, Optional[PyTree]]:
    """The downlink broadcast of ``g_server`` against the memory ``h``:
    per schedule group, or on the single downlink carrier. Its key comes
    off the ROUND rng (never a per-client fold): the broadcast is one
    identical message everywhere. ``memory=False`` is the naive broadcast
    of C(g) (``ef.downlink_sync``). Returns ``(g_est, h_new)``."""
    r_down = None if rng is None else jax.random.fold_in(rng, DOWNLINK_FOLD)
    with jax.named_scope(DOWNLINK):
        if efc.schedule is not None:
            return sched_lib.downlink_round_grouped(
                efc.schedule, g_server, h, r_down, memory=memory)
        return ef_lib.downlink_sync(
            carrier_lib.make(efc.down_carrier), efc.down_comp(), g_server,
            h, rng=r_down, memory=memory)


def ef_round_sharded(efc: EFConfig, grads: PyTree, ef_state: Dict,
                     rng: Optional[jax.Array], mesh, grads_specs: PyTree,
                     state_specs: Dict, eta: Optional[float] = None,
                     step: Optional[jax.Array] = None
                     ) -> Tuple[PyTree, Dict]:
    """shard_map EF sync: each device runs its client's update on its LOCAL param
    shard (per-shard Block-TopK — contractive with the same α, DESIGN.md §4), then
    the aggregation collective is issued *explicitly* by the carrier
    (core/carriers.py):

      'dense'  : psum(cᵢ)/n over the client axes — an all-reduce of d/tp words
                 per device (the paper-faithful wire format)
      'sparse' : all_gather of the local (values, block-local indices) over the
                 client axes — 2·dp·K/tp words per device — followed by a local
                 scatter-add (the beyond-paper wire format)
      'fused'  : dense aggregation, but the client chain ran as one Pallas pass

    This keeps compression 100% collective-free (no flatten-induced gathers) and
    makes the collective schedule ours rather than the SPMD partitioner's.
    Every collective of the aggregation goes through ``carriers.pmean`` /
    ``carriers.all_gather``: scoped ``ef_collective`` and recorded, one
    ``telemetry.collective_round`` per trace of this round.
    """
    collective_round()
    method = efc.method
    c_axes = efc.data_axes
    sched = efc.schedule
    carrier = carrier_lib.make(efc.carrier)
    if efc.overlap:
        carrier = dataclasses.replace(carrier, overlap=True)
    plan = carrier.plan(method, eta)

    n_total = 1
    for a in c_axes:
        n_total *= mesh.shape[a]
    mask_full = _participation_mask(efc, n_total, step)
    m_cohort = efc.participation.cohort_size(n_total) \
        if mask_full is not None else n_total

    # two-tier hierarchical aggregation (DESIGN.md §13): the intra hop
    # aggregates over the intra-pod axes only, then each pod's aggregator
    # runs the cross hop. A trivial cross (dense + identity) keeps the
    # legacy global collective verbatim — the flat-equivalence anchor.
    hops = efc.effective_hops
    trivial_cross = hops is None or hier_lib.cross_is_trivial(hops, sched)
    if hops is not None:
        if "pod" not in c_axes:
            raise ValueError(
                "hierarchical aggregation needs a 'pod' client axis; "
                f"got data_axes={c_axes}")
        if hops.pods != mesh.shape["pod"]:
            raise ValueError(
                f"hops.pods={hops.pods} must equal the mesh pod axis "
                f"({mesh.shape['pod']})")
    check_composition(efc, eta, n_total, mask_full)
    # the collective axes of the intra hop: everything when flat or when the
    # cross hop is trivial (legacy bits), the non-pod axes otherwise
    intra_axes = c_axes if trivial_cross \
        else tuple(a for a in c_axes if a != "pod")

    def client_index():
        # this device's global client index over the client axes
        idx = 0
        for a in c_axes:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx

    def client_leg(grads_l, clients_l, rng_l, mask_l=None):
        sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
        ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        g, cl = sq(grads_l), sq(clients_l)        # strip the client dim (local=1)
        # this client's scalar cohort entry: zero-masked wires make the
        # collective fold only the sampled cohort (C(0) = 0 exactly)
        mask_m = None if mask_l is None else mask_l[client_index()]

        # scopes as in ef_round: EF_CLIENT for the update and encode,
        # EF_AGGREGATE for the decode and the collective
        pmean = jax.named_scope(EF_AGGREGATE)(
            lambda t: jax.tree_util.tree_map(
                lambda m: carrier_lib.pmean(m, intra_axes), t))
        if sched is not None:
            # grouped engine: one wire (and one aggregation collective) per
            # group, each on its group's carrier/compressor
            msg_mean, new_cl = sched_lib.round_local(
                sched, method, g, cl, intra_axes, rng_l, eta,
                overlap=efc.overlap, mask=mask_m)
        elif plan == "fused":
            with jax.named_scope(EF_CLIENT):
                c_tree, new_cl = carrier.fused_update(method, g, cl, eta=eta)
                if mask_m is not None:
                    c_tree = part_lib.apply_mask(mask_m, c_tree)
            msg_mean = pmean(c_tree)
        elif plan == "fused_wire":
            # one mega-kernel launch per leaf: update + select + quantize +
            # EF-invariant integration; the aggregated mean comes back with
            # the new client state (aggregation needs the wire)
            msg_mean, new_cl = carrier.fused_wire_round(
                method, g, cl, eta=eta, axes=intra_axes)
        elif plan == "wire":
            with jax.named_scope(EF_CLIENT):
                deltas, ctx = method.pre_compress(g, cl, eta=eta)
                if mask_m is not None:
                    deltas = part_lib.apply_mask(mask_m, deltas)
            c_tree, msg_mean = carrier_lib.wire_round_local(
                carrier, method.compressor, deltas, intra_axes, rng_l)
            with jax.named_scope(EF_CLIENT):
                _, new_cl = method.post_compress(c_tree, ctx)
        else:
            # dense plan: aggregate the method's actual MESSAGE (for
            # wire_is_msg=False methods msg ≠ c, e.g. Abs ships γ·c), and go
            # through method.update so methods without a two-phase API
            # (neolithic, ideal) also run on the sharded path
            with jax.named_scope(EF_CLIENT):
                msg, new_cl = method.update(g, cl, rng_l, eta=eta)
                if mask_m is not None:
                    msg = part_lib.apply_mask(mask_m, msg)
            msg_mean = pmean(msg)
        if mask_m is not None:
            # Bells & Whistles: delta methods fold (1/n)Σ_S as-is, absolute
            # methods rescale to the cohort mean; non-sampled clients keep
            # their ENTIRE state tree (gᵢ, momentum, …) bit-frozen
            with jax.named_scope(EF_AGGREGATE):
                msg_mean = part_lib.rescale_message(method, msg_mean,
                                                    n_total, m_cohort)
            with jax.named_scope(EF_CLIENT):
                new_cl = part_lib.freeze_tree(mask_m, new_cl, cl)
        return ex(new_cl), msg_mean

    def fold_client(rng_l):
        # local client index for rng decorrelation
        if rng_l is None:
            return None
        return jax.random.fold_in(rng_l, client_index())

    server_specs = state_specs["server"]
    # conditional shard_map operands (same arity pattern for both: the
    # legacy path's jaxpr stays byte-stable) — the cohort mask as one
    # replicated (n,) array, or the pod EF memory sharded over the pod axis.
    # Mutually exclusive: sampled × hops is a construction error above.
    extra_args = () if mask_full is None else (mask_full,)
    extra_specs = () if mask_full is None else (P(),)
    if hops is not None:
        extra_args = (ef_state["pods"],)
        extra_specs = (state_specs["pods"],)
    pod_out_specs = () if hops is None else (state_specs["pods"],)

    def split_rest(rest):
        if hops is not None:
            return rest[0], None
        return None, (rest[0] if rest else None)

    def pod_leg(msg_mean, pods_l, rng_l):
        """The pod tier, per device: fold the intra-hop mean into this pod's
        target, run the cross hop (per-pod rng = fold_in(fold_in(rng,
        CROSS_FOLD), pod_index) — off the ROUND rng, like the downlink
        fold), and return the server-bound message — pmean over the pod
        axis of each pod's contribution — plus the new pod memory. Under a
        trivial cross msg_mean is already the legacy GLOBAL mean and the
        pod memory is bookkeeping only."""
        sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
        ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        st = sq(pods_l)
        if trivial_cross:
            return msg_mean, ex(
                hier_lib.trivial_bookkeeping(method, st, msg_mean))
        r_pod = None if rng_l is None else jax.random.fold_in(
            jax.random.fold_in(rng_l, CROSS_FOLD),
            jax.lax.axis_index("pod"))
        t_new = hier_lib.pod_target(method, st["t"], msg_mean)
        b_new = hier_lib.cross_sync(hops, sched, t_new, st["b"], r_pod)
        pod_msg = hier_lib.pod_message(method, st["b"], b_new)
        server_msg = jax.tree_util.tree_map(
            lambda m: carrier_lib.pmean(m, ("pod",)), pod_msg)
        return server_msg, ex({"t": t_new, "b": b_new})

    if efc.has_downlink:
        def body(grads_l, clients_l, server_l, h_l, rng_l, *rest):
            pods_l, mask_l = split_rest(rest)
            new_cl, msg_mean = client_leg(
                grads_l, clients_l, fold_client(rng_l), mask_l)
            with jax.named_scope(EF_AGGREGATE):
                if hops is not None:
                    msg_mean, new_pods = pod_leg(msg_mean, pods_l, rng_l)
                new_server = ef_lib.server_step(method, server_l, msg_mean)
            # every device runs the same encode of the replicated-in-value
            # new_server (that IS the broadcast — the encoded wire is what
            # travels) and the same decode its client would run. Sampling
            # composes for free: h is server-side, so a client absent for k
            # rounds still integrated every broadcast and re-enters in sync
            g_est, h_new = downlink_leg(efc, new_server, h_l, rng_l)
            out = (new_cl, new_server, h_new, g_est)
            return out + ((new_pods,) if hops is not None else ())

        h_specs = state_specs.get("h", server_specs)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(grads_specs, state_specs["clients"], server_specs,
                      h_specs, P()) + extra_specs,
            out_specs=(state_specs["clients"], server_specs, h_specs,
                       server_specs) + pod_out_specs,
            check_vma=False)
        out = fn(
            grads, ef_state["clients"], ef_state["server"], ef_state["h"],
            rng, *extra_args)
        new_clients, new_server, h_new, g_est = out[:4]
        new_state = {"clients": new_clients, "server": new_server,
                     "h": h_new}
        if hops is not None:
            new_state["pods"] = out[4]
        return g_est, new_state

    def body(grads_l, clients_l, server_l, rng_l, *rest):
        pods_l, mask_l = split_rest(rest)
        new_cl, msg_mean = client_leg(
            grads_l, clients_l, fold_client(rng_l), mask_l)
        with jax.named_scope(EF_AGGREGATE):
            if hops is not None:
                msg_mean, new_pods = pod_leg(msg_mean, pods_l, rng_l)
            new_server = ef_lib.server_step(method, server_l, msg_mean)
        out = (new_cl, new_server, msg_mean)
        return out + ((new_pods,) if hops is not None else ())

    out_specs = (state_specs["clients"], server_specs, server_specs) \
        + pod_out_specs
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(grads_specs, state_specs["clients"], server_specs, P())
        + extra_specs,
        out_specs=out_specs, check_vma=False)
    out = fn(grads, ef_state["clients"], ef_state["server"], rng,
             *extra_args)
    new_clients, new_server = out[0], out[1]
    new_state = {"clients": new_clients, "server": new_server}
    if hops is not None:
        new_state["pods"] = out[3]
    return new_server, new_state


def uplink_round(efc: EFConfig, grads: PyTree, ef_state: Dict,
                 rng: Optional[jax.Array], eta: Optional[float] = None,
                 step: Optional[jax.Array] = None) -> Tuple[PyTree, Dict]:
    """The vmap round up to the server: the clients' update and wire, the
    cohort, the pod tier and the server step. grads: per-client (dp
    leading). Returns (new server estimate, new ef_state without 'h')."""
    method, dp = efc.method, jax.tree_util.tree_leaves(grads)[0].shape[0]
    clients, server = ef_state["clients"], ef_state["server"]
    carrier = carrier_lib.make(efc.carrier)
    plan = carrier.plan(method, eta)
    rngs = jax.random.split(rng, dp) if rng is not None else None
    mask = _participation_mask(efc, dp, step)

    # two-tier hierarchy (DESIGN.md §13): under a NON-trivial cross hop the
    # intra aggregation produces per-pod means (pods on a leading axis —
    # pod-major contiguous client blocks) instead of the global mean; a
    # trivial cross keeps the legacy global aggregation ops verbatim
    hops = efc.effective_hops
    trivial_cross = hops is None or hier_lib.cross_is_trivial(
        hops, efc.schedule)
    want_pods = hops is not None and not trivial_cross
    check_composition(efc, eta, dp, mask)
    agg = jax.named_scope(EF_AGGREGATE)(
        (lambda t: hier_lib.pod_mean(t, hops.pods)) if want_pods
        else (lambda t: jax.tree_util.tree_map(lambda m: m.mean(0), t)))

    # scopes: the clients' update, compression and encode run under
    # EF_CLIENT, the decode and the mean over clients under EF_AGGREGATE
    # (the schedule's and the carriers' shared legs scope themselves)
    if efc.schedule is not None:
        msg_mean, new_clients = sched_lib.round_batched(
            efc.schedule, method, grads, clients, dp, rng, eta, mask=mask,
            pods=hops.pods if want_pods else 1)
    elif plan == "fused":
        with jax.named_scope(EF_CLIENT):
            c_tree, new_clients = carrier.fused_update(
                method, grads, clients, eta=eta, batched=True)
            if mask is not None:
                c_tree = part_lib.apply_mask(mask, c_tree)
        msg_mean = agg(c_tree)
    elif plan == "fused_wire":
        msg_mean, new_clients = carrier.fused_wire_round(
            method, grads, clients, eta=eta, batched=True, dp=dp)
    elif plan == "wire":
        with jax.named_scope(EF_CLIENT):
            deltas, ctxs = jax.vmap(
                lambda g, s: method.pre_compress(g, s, eta=eta))(
                grads, clients)
            if mask is not None:
                # zero-masked wires: C(0) = 0 exactly, so the carrier's own
                # aggregation folds only the sampled cohort
                deltas = part_lib.apply_mask(mask, deltas)
        c_tree, wire_mean = carrier_lib.wire_round_batched(
            carrier, method.compressor, deltas, dp)
        # non-trivial hops pod-mean the per-client messages (local_c IS the
        # decode of what traveled); the unused global aggregate is DCE'd
        msg_mean = agg(c_tree) if want_pods else wire_mean
        with jax.named_scope(EF_CLIENT):
            _, new_clients = jax.vmap(method.post_compress)(c_tree, ctxs)
    else:
        def upd(g, s, r):
            return method.update(g, s, r, eta=eta)
        with jax.named_scope(EF_CLIENT):
            if rngs is None:
                msgs, new_clients = jax.vmap(lambda g, s: upd(g, s, None))(
                    grads, clients)
            else:
                msgs, new_clients = jax.vmap(upd)(grads, clients, rngs)
            if mask is not None:
                msgs = part_lib.apply_mask(mask, msgs)
        msg_mean = agg(msgs)

    if mask is not None:
        # Bells & Whistles: delta methods fold (1/n)Σ_S as-is, absolute
        # methods rescale to the cohort mean; non-sampled clients keep
        # their ENTIRE state tree (gᵢ, momentum, …) bit-frozen
        with jax.named_scope(EF_AGGREGATE):
            msg_mean = part_lib.rescale_message(
                method, msg_mean, dp, efc.participation.cohort_size(dp))
        with jax.named_scope(EF_CLIENT):
            new_clients = part_lib.freeze_tree(mask, new_clients, clients)
    with jax.named_scope(EF_AGGREGATE):
        if want_pods:
            new_pods, new_server = hier_lib.round_pods_batched(
                hops, efc.schedule, method, msg_mean, ef_state["pods"],
                server, rng)
        else:
            new_server = ef_lib.server_step(method, server, msg_mean)
        new_state = {"clients": new_clients, "server": new_server}
        if hops is not None:
            new_state["pods"] = new_pods if want_pods else \
                hier_lib.trivial_bookkeeping(method, ef_state["pods"],
                                             msg_mean)
    return new_server, new_state


def ef_round(efc: EFConfig, grads: PyTree, ef_state: Dict,
             rng: Optional[jax.Array], eta: Optional[float] = None,
             step: Optional[jax.Array] = None) -> Tuple[PyTree, Dict]:
    """vmap EF sync (single-device runs, exact global-TopK semantics): the
    uplink round, then the downlink leg when there is one. grads:
    per-client (dp leading). Returns (gᵗ⁺¹ estimate, new ef_state)."""
    new_server, new_state = uplink_round(efc, grads, ef_state, rng, eta,
                                         step)
    if not efc.has_downlink:
        return new_server, new_state
    g_est, new_state["h"] = downlink_leg(efc, new_server, ef_state["h"], rng)
    return g_est, new_state


# ---------------------------------------------------------------------------
# full train step (composed in launch/train.py; kept here for reuse/tests)
# ---------------------------------------------------------------------------

def make_train_step(loss_fn: Callable, efc: EFConfig, optimizer, dp: int,
                    eta: Optional[float] = None, mesh=None,
                    grads_specs: Optional[PyTree] = None,
                    state_specs: Optional[Dict] = None):
    """Returns train_step(params, opt_state, ef_state, batch, rng, step).
    With mesh+specs, the EF sync runs in explicit shard_map (production path);
    otherwise the vmap path (single-device tests, exact global-TopK semantics)."""
    from repro.optim.optimizer import apply_updates

    def train_step(params, opt_state, ef_state, batch, rng, step):
        # the scope sits outside vmap and autodiff, so forward, backward and
        # remat ops all carry it
        with jax.named_scope(MODEL):
            loss, aux, grads = per_client_value_and_grad(
                loss_fn, params, batch, dp)
        r_comp = jax.random.fold_in(rng, 1)
        if mesh is not None and grads_specs is not None:
            g_est, ef_state = ef_round_sharded(
                efc, grads, ef_state, r_comp, mesh, grads_specs, state_specs,
                eta=eta, step=step)
        else:
            g_est, ef_state = ef_round(efc, grads, ef_state, r_comp, eta=eta,
                                       step=step)
        with jax.named_scope(OPTIMIZER):
            updates, opt_state = optimizer.update(g_est, opt_state, params,
                                                  step)
            params = apply_updates(params, updates)
        # the estimate's norm reads what the server step writes, and XLA
        # fuses the two: it runs under the aggregation's scope
        with jax.named_scope(EF_AGGREGATE):
            g_norm = jnp.sqrt(ef_lib.tree_norm_sq(g_est))
        metrics = {"loss": loss, "g_norm": g_norm}
        return params, opt_state, ef_state, metrics

    return train_step
