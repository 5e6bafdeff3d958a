"""Per-parameter-group compression schedules (DESIGN.md §9).

The paper's contractive-compressor framework (Definition 1) is per-message,
not per-model: nothing in the EF21-SGDM analysis requires every parameter
tensor to share one compressor, and a product of contractive maps is
contractive with α = min over factors (Richtárik et al. 2021), so EF21's
theory composes over any partition of the parameter pytree. Real systems
exploit exactly that freedom — norms/biases are a rounding error on the wire
and ship dense, embeddings tolerate aggressive quantization, attention/MLP
matrices are where TopK earns its keep.

:class:`CompressionSchedule` makes the partition first-class: an ordered
tuple of :class:`Group` entries, each naming a path pattern plus its own
compressor, uplink carrier, downlink carrier/compressor and EF-state dtype.
Leaves are assigned **first-match-wins** against the pattern order, and the
last group MUST be the catch-all ``"*"`` — so every leaf lands in exactly
one group by construction. Patterns are ``|``-separated substring tokens
matched against the leaf's ``/``-joined lower-cased key path (``"norm|bias"``
matches ``layers/mlp/norm``; ``"*"`` matches everything).

This module also hosts the *grouped execution engine* both runtimes
dispatch through (``ef_round``, which core/simulate.py drives, and
``ef_round_sharded``, in core/distributed.py): per group, the existing
single-compressor machinery runs unchanged on that group's leaf list — the
same pre_compress → C(·) → post_compress chain, the same carrier plans
('dense' | 'wire' | 'fused' | 'fused_wire'), the same downlink broadcast
leg — and the
results are scattered back into the full tree. A uniform single-group
schedule therefore executes the *identical* operation sequence (including
rng folding: the group rng is the round rng untouched when there is only
one group) and is bit-identical to the legacy single-compressor path — the
regression anchor tests/test_schedule.py pins.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import carriers as carrier_lib
from repro.core import compressors as comp_lib
from repro.core import ef as ef_lib
from repro.core import participation as part_lib
from repro.telemetry import EF_AGGREGATE, EF_CLIENT

PyTree = Any

# characters the flag grammar reserves — a pattern containing one could never
# round-trip through `--schedule "pat=carrier:ratio@comp,…"`
PATTERN_RESERVED = set("=,:@")

# per-group EF-state dtype universe ('float32' exists so one group can force
# full precision under a bfloat16 spec-level default)
GROUP_STATE_DTYPES = (None, "bfloat16", "float32")


def pattern_token_errors(pattern: str) -> List[str]:
    """Malformed-token diagnostics shared by both validators (the schedule's
    own ``__post_init__`` and the jax-free RunSpec mirror). An EMPTY token —
    a ``'norm|'`` typo — is a substring of every path and would silently
    swallow the whole model into one group; a ``'*'`` token inside a
    composite pattern would shadow every later group the same way."""
    toks = pattern.split("|")
    errs = []
    if any(not t for t in toks):
        errs.append("empty '|' token (matches every leaf)")
    if "*" in toks and pattern != "*":
        errs.append("'*' may only be the standalone catch-all pattern")
    return errs


def pattern_matches(pattern: str, path: str) -> bool:
    """``|``-separated substring tokens; ``*`` matches everything. Matching
    is case-insensitive (leaf paths are lower-cased, so tokens must be
    too — a pattern written in a tree's literal mixed case still hits)."""
    for tok in pattern.lower().split("|"):
        if tok == "*" or tok in path:
            return True
    return False


def _key_str(entry) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def leaf_paths(tree: PyTree) -> Tuple[str, ...]:
    """The ``/``-joined lower-cased key path of every leaf, in
    ``tree_flatten`` order — the strings schedule patterns match against."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return tuple("/".join(_key_str(k) for k in path).lower()
                 for path, _ in flat)


@dataclasses.dataclass(frozen=True)
class Group:
    """One partition cell: pattern + its full transport configuration.
    Frozen/hashable → a schedule is usable as a jit static argument."""

    pattern: str
    compressor: comp_lib.Compressor = comp_lib.Identity()
    carrier: str = "dense"
    down_carrier: str = "dense"
    down_compressor: Optional[comp_lib.Compressor] = None
    state_dtype: Optional[str] = None   # None → inherit the method's
    # per-hop fields (DESIGN.md §13): under a hierarchical topology
    # (EFConfig.hops) the CROSS-pod hop of this group's leaves ships
    # C_cross(t_pod − b_pod) on its own carrier/compressor. The defaults are
    # the trivial cross (dense + identity): the pod aggregator is
    # transparent for this group and the flat bits are preserved.
    cross_carrier: str = "dense"
    cross_compressor: Optional[comp_lib.Compressor] = None

    @property
    def name(self) -> str:
        return self.pattern

    @property
    def has_downlink(self) -> bool:
        return self.down_carrier != "dense" or self.down_compressor is not None

    def down_comp(self) -> comp_lib.Compressor:
        return (self.down_compressor if self.down_compressor is not None
                else comp_lib.Identity())

    @property
    def trivial_cross(self) -> bool:
        return (self.cross_carrier == "dense"
                and isinstance(self.cross_comp(), comp_lib.Identity))

    def cross_comp(self) -> comp_lib.Compressor:
        return (self.cross_compressor if self.cross_compressor is not None
                else comp_lib.Identity())


@dataclasses.dataclass(frozen=True)
class CompressionSchedule:
    """An ordered, first-match-wins partition of the param pytree. The last
    group must be the mandatory catch-all ``"*"`` so resolution is total."""

    groups: Tuple[Group, ...] = ()

    def __post_init__(self):
        errs: List[str] = []
        if not self.groups:
            errs.append("a schedule needs at least one group")
        else:
            if self.groups[-1].pattern != "*":
                errs.append("the last group must be the catch-all '*' "
                            f"(got {self.groups[-1].pattern!r}) so every "
                            "leaf lands in exactly one group")
            seen = set()
            for i, g in enumerate(self.groups):
                if not g.pattern:
                    errs.append(f"group {i} has an empty pattern")
                if g.pattern == "*" and i != len(self.groups) - 1:
                    errs.append("the catch-all '*' must be the LAST group "
                                "(first-match-wins would shadow everything "
                                "after it)")
                if g.pattern in seen:
                    errs.append(f"duplicate group pattern {g.pattern!r}")
                seen.add(g.pattern)
                bad = PATTERN_RESERVED & set(g.pattern)
                if bad:
                    errs.append(f"pattern {g.pattern!r} uses reserved "
                                f"characters {sorted(bad)}")
                errs.extend(f"group {g.pattern!r}: {e}"
                            for e in pattern_token_errors(g.pattern))
                if g.carrier not in carrier_lib.REGISTRY:
                    errs.append(f"group {g.pattern!r}: unknown carrier "
                                f"{g.carrier!r}")
                if g.down_carrier not in carrier_lib.REGISTRY \
                        or g.down_carrier == "fused":
                    errs.append(f"group {g.pattern!r}: downlink carrier "
                                f"{g.down_carrier!r} is not a thing (the "
                                "fused kernel is the uplink client update)")
                if g.cross_carrier not in carrier_lib.REGISTRY \
                        or g.cross_carrier == "fused":
                    errs.append(f"group {g.pattern!r}: cross-pod carrier "
                                f"{g.cross_carrier!r} is not a thing (the "
                                "cross hop is one message per pod — same "
                                "rules as the downlink broadcast)")
                if g.state_dtype not in GROUP_STATE_DTYPES:
                    errs.append(f"group {g.pattern!r}: state_dtype "
                                f"{g.state_dtype!r} not in "
                                f"{list(GROUP_STATE_DTYPES)}")
        if errs:
            raise ValueError("invalid CompressionSchedule:\n  - "
                             + "\n  - ".join(errs))

    @classmethod
    def uniform(cls, compressor: comp_lib.Compressor, carrier: str = "dense",
                down_carrier: str = "dense",
                down_compressor: Optional[comp_lib.Compressor] = None,
                state_dtype: Optional[str] = None,
                cross_carrier: str = "dense",
                cross_compressor: Optional[comp_lib.Compressor] = None
                ) -> "CompressionSchedule":
        """The one-group schedule equivalent to today's single-knob config —
        the regression anchor (bit-identical to the legacy path)."""
        return cls((Group(pattern="*", compressor=compressor, carrier=carrier,
                          down_carrier=down_carrier,
                          down_compressor=down_compressor,
                          state_dtype=state_dtype,
                          cross_carrier=cross_carrier,
                          cross_compressor=cross_compressor),))

    @property
    def has_downlink(self) -> bool:
        return any(g.has_downlink for g in self.groups)

    def match(self, path: str) -> int:
        """First-match-wins group index for one leaf path."""
        for i, g in enumerate(self.groups):
            if pattern_matches(g.pattern, path):
                return i
        raise ValueError(             # unreachable: '*' is mandatory
            f"leaf {path!r} matched no group (no catch-all?)")

    def resolve(self, tree: PyTree) -> Tuple[int, ...]:
        """Per-leaf group index in ``tree_flatten`` order. Every leaf lands
        in exactly one group (first-match-wins over a total pattern list)."""
        return tuple(self.match(p) for p in leaf_paths(tree))


# ---------------------------------------------------------------------------
# per-group method view
# ---------------------------------------------------------------------------

def group_method(method: "ef_lib.Method", grp: Group) -> "ef_lib.Method":
    """The method as one group sees it: same semantics, the group's
    compressor and EF-state dtype."""
    if grp.state_dtype is None:
        dt = method.state_dtype
    elif grp.state_dtype == "bfloat16":
        dt = jnp.bfloat16
    else:
        dt = jnp.float32
    return dataclasses.replace(method, compressor=grp.compressor,
                               state_dtype=dt)


# ---------------------------------------------------------------------------
# tree partition plumbing
# ---------------------------------------------------------------------------

def _leaves(tree: PyTree) -> List:
    return jax.tree_util.tree_flatten(tree)[0]


def _group_indices(schedule: CompressionSchedule, base: PyTree
                   ) -> List[Tuple[int, ...]]:
    gids = schedule.resolve(base)
    return [tuple(i for i, g in enumerate(gids) if g == gi)
            for gi in range(len(schedule.groups))]


def _take(tree: PyTree, ii: Tuple[int, ...]) -> List:
    leaves = _leaves(tree)
    return [leaves[i] for i in ii]


def _take_grads(grads: PyTree, method, ii: Tuple[int, ...]):
    """Grads for one group — a leaf list, or a pair of leaf lists for
    paired-gradient methods (STORM / ideal)."""
    if method.needs_paired_grads:
        return (_take(grads[0], ii), _take(grads[1], ii))
    return _take(grads, ii)


def _take_state(state: Dict, ii: Tuple[int, ...]) -> Dict:
    return {k: _take(v, ii) for k, v in state.items()}


def _scatter(out: List, ii: Tuple[int, ...], parts: List) -> None:
    for i, leaf in zip(ii, parts):
        out[i] = leaf


def _group_rng(rng, gi: int, n_groups: int):
    """One group → the round rng untouched (bit-identity with the legacy
    single-compressor path); several → decorrelate by group index."""
    if rng is None or n_groups == 1:
        return rng
    return jax.random.fold_in(rng, gi)


# ---------------------------------------------------------------------------
# EF state init, grouped
# ---------------------------------------------------------------------------

def init_state_grouped(schedule: CompressionSchedule, method,
                       params_like: PyTree,
                       init_grads: Optional[PyTree] = None) -> Dict:
    """``method.init`` per group (per-group EF-state dtype), merged back onto
    the full param treedef. One client's state — callers vmap for the client
    axis exactly as with ``method.init``."""
    treedef = jax.tree_util.tree_structure(params_like)
    n = treedef.num_leaves
    idx = _group_indices(schedule, params_like)
    merged: Optional[Dict[str, List]] = None
    for gi, grp in enumerate(schedule.groups):
        ii = idx[gi]
        if not ii:
            continue
        m_g = group_method(method, grp)
        g0 = None if init_grads is None else _take(init_grads, ii)
        st = m_g.init(_take(params_like, ii), init_grads=g0)
        if merged is None:
            merged = {k: [None] * n for k in st}
        for k, part in st.items():
            _scatter(merged[k], ii, part)
    if not merged:
        return {}
    return {k: jax.tree_util.tree_unflatten(treedef, v)
            for k, v in merged.items()}


# ---------------------------------------------------------------------------
# one grouped client round — shared scaffolding + the two layouts
# ---------------------------------------------------------------------------

def _grouped_round(schedule: CompressionSchedule, method, grads: PyTree,
                   states: Dict, rng, eta, leg,
                   overlap: bool = False) -> Tuple[PyTree, Dict]:
    """The scaffolding both layouts share: resolve leaves → per-group take →
    ``leg(m_g, carrier, plan, grads_g, states_g, r_g) -> (agg_g, new_st)`` →
    scatter-merge back onto the full treedef. Keeping this in ONE place is
    what keeps the vmap and shard_map runtimes mechanically equivalent —
    only the per-plan leg bodies (collectives vs leading-axis means) differ.
    Returns ``(msg_mean, new_states)``."""
    base = grads[0] if method.needs_paired_grads else grads
    treedef = jax.tree_util.tree_structure(base)
    n_leaves = treedef.num_leaves
    idx = _group_indices(schedule, base)
    ng = len(schedule.groups)

    agg_out: List = [None] * n_leaves
    state_out: Optional[Dict[str, List]] = None
    for gi, grp in enumerate(schedule.groups):
        ii = idx[gi]
        if not ii:
            continue
        m_g = group_method(method, grp)
        carrier = carrier_lib.make(grp.carrier)
        if overlap:
            carrier = dataclasses.replace(carrier, overlap=True)
        plan = carrier.plan(m_g, eta)
        agg_g, new_st = leg(m_g, carrier, plan,
                            _take_grads(grads, method, ii),
                            _take_state(states, ii),
                            _group_rng(rng, gi, ng))
        _scatter(agg_out, ii, agg_g)
        if state_out is None:
            state_out = {k: [None] * n_leaves for k in new_st}
        for k, part in new_st.items():
            _scatter(state_out[k], ii, part)

    msg_mean = jax.tree_util.tree_unflatten(treedef, agg_out)
    if not state_out:
        return msg_mean, {}
    new_states = {k: jax.tree_util.tree_unflatten(treedef, v)
                  for k, v in state_out.items()}
    return msg_mean, new_states


def round_batched(schedule: CompressionSchedule, method, grads: PyTree,
                  states: Dict, dp: int, rng, eta=None, mask=None,
                  pods: int = 1) -> Tuple[PyTree, Dict]:
    """Per-group client legs with clients on a leading axis (the vmap
    runtimes). Each group independently picks its carrier's plan and builds
    its own wire; results merge back onto the full treedef. ``mask`` is an
    optional (dp,) cohort mask (DESIGN.md §11): each group zeroes the
    non-sampled clients' contribution before its own aggregation — the
    freeze/rescale postlude stays at the CALLER (one method/mode across all
    groups). ``pods > 1`` (DESIGN.md §13) returns PER-POD means on a leading
    pods axis (pod-major client blocks) instead of the global mean — the
    intra hop of the hierarchical topology; the caller's pod tier owns the
    cross hop. The caller has checked the composition
    (``distributed.check_composition``). Returns ``(msg_mean, new_states)``."""
    if pods > 1 and dp % pods:
        raise ValueError(f"pods={pods} must divide the client count {dp}")

    @jax.named_scope(EF_AGGREGATE)
    def agg(leaves_list):
        if pods > 1:
            m = dp // pods
            return jax.tree_util.tree_map(
                lambda c: c.reshape(pods, m, *c.shape[1:]).mean(1),
                leaves_list)
        return jax.tree_util.tree_map(lambda c: c.mean(0), leaves_list)

    # scopes as in distributed.ef_round: EF_CLIENT for the update and
    # encode, EF_AGGREGATE for the decode and the mean over clients
    def leg(m_g, carrier, plan, grads_g, states_g, r_g):
        if plan == "fused":
            with jax.named_scope(EF_CLIENT):
                c_tree, new_st = carrier.fused_update(
                    m_g, grads_g, states_g, eta=eta, batched=True)
                if mask is not None:
                    c_tree = part_lib.apply_mask(mask, c_tree)
            return agg(c_tree), new_st
        if plan == "fused_wire":
            return carrier.fused_wire_round(
                m_g, grads_g, states_g, eta=eta, batched=True, dp=dp)
        if plan == "wire":
            with jax.named_scope(EF_CLIENT):
                deltas, ctxs = jax.vmap(
                    lambda g, s, m=m_g: m.pre_compress(g, s, eta=eta))(
                    grads_g, states_g)
                if mask is not None:
                    deltas = part_lib.apply_mask(mask, deltas)
            c_tree, agg_g = carrier_lib.wire_round_batched(
                carrier, m_g.compressor, deltas, dp)
            with jax.named_scope(EF_CLIENT):
                _, new_st = jax.vmap(m_g.post_compress)(c_tree, ctxs)
            # per-pod means of the decoded client messages; the global
            # aggregate the carrier built is unused and DCE'd under jit
            return (agg(c_tree) if pods > 1 else agg_g), new_st
        with jax.named_scope(EF_CLIENT):
            if r_g is None:
                msgs, new_st = jax.vmap(
                    lambda g, s, m=m_g: m.update(g, s, None, eta=eta))(
                    grads_g, states_g)
            else:
                rngs = jax.random.split(r_g, dp)
                msgs, new_st = jax.vmap(
                    lambda g, s, r, m=m_g: m.update(g, s, r, eta=eta))(
                    grads_g, states_g, rngs)
            if mask is not None:
                msgs = part_lib.apply_mask(mask, msgs)
        return agg(msgs), new_st

    return _grouped_round(schedule, method, grads, states, rng, eta, leg)


def round_local(schedule: CompressionSchedule, method, grads: PyTree,
                states: Dict, axes: Tuple[str, ...], rng, eta=None,
                overlap: bool = False, mask=None) -> Tuple[PyTree, Dict]:
    """Per-group client legs with client-local leaves and explicit named-axis
    collectives (``ef_round_sharded``). ``overlap`` turns each group
    carrier's gather-wire aggregation into the ppermute ring
    (carriers.ring_all_gather — bit-identical transport). ``mask`` is this
    device's SCALAR cohort membership (DESIGN.md §11): each group zeroes a
    non-sampled device's contribution before its collective — the
    freeze/rescale postlude stays at the CALLER. Returns
    ``(msg_mean, new_states)``."""
    pmean = jax.named_scope(EF_AGGREGATE)(
        lambda t: jax.tree_util.tree_map(
            lambda m: carrier_lib.pmean(m, axes), t))

    def leg(m_g, carrier, plan, grads_g, states_g, r_g):
        if plan == "fused":
            with jax.named_scope(EF_CLIENT):
                c_tree, new_st = carrier.fused_update(
                    m_g, grads_g, states_g, eta=eta)
                if mask is not None:
                    c_tree = part_lib.apply_mask(mask, c_tree)
            return pmean(c_tree), new_st
        if plan == "fused_wire":
            return carrier.fused_wire_round(
                m_g, grads_g, states_g, eta=eta, axes=axes)
        if plan == "wire":
            with jax.named_scope(EF_CLIENT):
                deltas, ctx = m_g.pre_compress(grads_g, states_g, eta=eta)
                if mask is not None:
                    deltas = part_lib.apply_mask(mask, deltas)
            c_tree, agg_g = carrier_lib.wire_round_local(
                carrier, m_g.compressor, deltas, axes, r_g)
            with jax.named_scope(EF_CLIENT):
                _, new_st = m_g.post_compress(c_tree, ctx)
            return agg_g, new_st
        with jax.named_scope(EF_CLIENT):
            msg, new_st = m_g.update(grads_g, states_g, r_g, eta=eta)
            if mask is not None:
                msg = part_lib.apply_mask(mask, msg)
        return pmean(msg), new_st

    return _grouped_round(schedule, method, grads, states, rng, eta, leg,
                          overlap=overlap)


# ---------------------------------------------------------------------------
# grouped downlink (server → client broadcast)
# ---------------------------------------------------------------------------

def downlink_round_grouped(schedule: CompressionSchedule, g_server: PyTree,
                           h: PyTree, rng, memory: bool = True
                           ) -> Tuple[PyTree, PyTree]:
    """Per-group downlink legs. Groups WITH a downlink carrier run the exact
    ``ef.downlink_sync`` semantics on their leaves (broadcast the wire of
    C(g − h), everyone integrates the decode); groups without ship the
    implicit dense broadcast — g_est is g_server and h simply tracks it.
    Returns ``(g_est, h_new)`` on the full treedef."""
    treedef = jax.tree_util.tree_structure(g_server)
    n_leaves = treedef.num_leaves
    idx = _group_indices(schedule, g_server)
    ng = len(schedule.groups)

    est_out: List = [None] * n_leaves
    h_out: List = [None] * n_leaves
    for gi, grp in enumerate(schedule.groups):
        ii = idx[gi]
        if not ii:
            continue
        s_g = _take(g_server, ii)
        if not grp.has_downlink:
            _scatter(est_out, ii, s_g)
            _scatter(h_out, ii, s_g)
            continue
        car = carrier_lib.make(grp.down_carrier)
        comp = grp.down_comp()
        r_g = _group_rng(rng, gi, ng)
        h_g = _take(h, ii)
        est_g, h_new_g = ef_lib.downlink_sync(car, comp, s_g, h_g, rng=r_g,
                                              memory=memory)
        _scatter(est_out, ii, est_g)
        _scatter(h_out, ii, h_new_g if h_new_g is not None else est_g)

    return (jax.tree_util.tree_unflatten(treedef, est_out),
            jax.tree_util.tree_unflatten(treedef, h_out))


# ---------------------------------------------------------------------------
# grouped cross-pod hop (pod aggregator → server, DESIGN.md §13)
# ---------------------------------------------------------------------------

def cross_round_grouped(schedule: CompressionSchedule, t_new: PyTree,
                        b: PyTree, rng) -> PyTree:
    """Per-group CROSS-pod hop for ONE pod aggregator: groups with a
    non-trivial cross carrier ship C_cross(t' − b) and integrate the decode
    (the exact ``ef.downlink_sync`` semantics — the uplink twin of the §8
    broadcast memory); trivial groups are transparent, ``b' = t'``
    bit-exactly. ``rng`` is the pod's cross rng (already folded with
    CROSS_FOLD and the pod index by the caller); groups decorrelate via the
    same ``_group_rng`` fold every other grouped leg uses. Returns the new
    broadcast state ``b'`` on the full treedef."""
    treedef = jax.tree_util.tree_structure(t_new)
    n_leaves = treedef.num_leaves
    idx = _group_indices(schedule, t_new)
    ng = len(schedule.groups)

    out: List = [None] * n_leaves
    for gi, grp in enumerate(schedule.groups):
        ii = idx[gi]
        if not ii:
            continue
        t_g = _take(t_new, ii)
        if grp.trivial_cross:
            _scatter(out, ii, t_g)
            continue
        car = carrier_lib.make(grp.cross_carrier)
        _, b_new_g = ef_lib.downlink_sync(car, grp.cross_comp(), t_g,
                                          _take(b, ii),
                                          rng=_group_rng(rng, gi, ng))
        _scatter(out, ii, b_new_g)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# accounting — per-group wire words (DESIGN.md §9 rules)
# ---------------------------------------------------------------------------

def wire_words_tree(schedule: CompressionSchedule, method, tree: PyTree,
                    direction: str = "up", eta=None
                    ) -> Tuple[Tuple[float, ...], float]:
    """Honest per-client wire words of one message over ``tree``, summed per
    group and in total. Follows the plan that would EXECUTE: a group whose
    carrier degrades to the dense plan (or fuses — the fused wire is dense)
    ships its dense word count. ``direction='down'`` counts the broadcast
    instead (a group with no downlink honestly ships its dense leaves);
    ``direction='cross'`` counts ONE pod aggregator's cross-pod message
    (DESIGN.md §13 — callers multiply by pods)."""
    idx = _group_indices(schedule, tree)
    leaves = _leaves(tree)
    per: List[float] = []
    for gi, grp in enumerate(schedule.groups):
        total = 0.0
        if direction == "down":
            car = carrier_lib.make(grp.down_carrier)
            comp = grp.down_comp()
            for i in idx[gi]:
                d = int(leaves[i].size)
                total += (carrier_lib.downlink_words(car, comp, d)
                          if grp.has_downlink else float(d))
        elif direction == "cross":
            car = carrier_lib.make(grp.cross_carrier)
            comp = grp.cross_comp()
            for i in idx[gi]:
                d = int(leaves[i].size)
                total += (float(d) if grp.trivial_cross
                          else carrier_lib.downlink_words(car, comp, d))
        else:
            m_g = group_method(method, grp)
            car = carrier_lib.make(grp.carrier)
            plan = car.plan(m_g, eta)
            for i in idx[gi]:
                d = int(leaves[i].size)
                # the fused_wire plan ships the quantized payload, so it
                # counts the carrier's wire words exactly like 'wire'
                total += (car.wire_words(m_g.compressor, d)
                          if plan in ("wire", "fused_wire") else float(d))
        per.append(total)
    return tuple(per), float(sum(per))


def coords_tree(schedule: CompressionSchedule, method, tree: PyTree) -> float:
    """Idealized transmitted-coordinate count (the paper's x-axis), summed
    over groups — the schedule form of ``Method.coords_per_message(d)``."""
    idx = _group_indices(schedule, tree)
    leaves = _leaves(tree)
    total = 0.0
    for gi, grp in enumerate(schedule.groups):
        m_g = group_method(method, grp)
        for i in idx[gi]:
            total += m_g.coords_per_message(int(leaves[i].size))
    return total


def alpha_min(schedule: CompressionSchedule, tree: PyTree) -> float:
    """The composed contraction parameter: a product of contractive maps over
    a partition is contractive with α = min over the factors."""
    idx = _group_indices(schedule, tree)
    leaves = _leaves(tree)
    alphas = []
    for gi, grp in enumerate(schedule.groups):
        for i in idx[gi]:
            alphas.append(grp.compressor.alpha(int(leaves[i].size)))
    return min(alphas) if alphas else 1.0


# ---------------------------------------------------------------------------
# the resolved group table (launch surfaces print this)
# ---------------------------------------------------------------------------

def plan_table(schedule: CompressionSchedule, method, tree: PyTree,
               eta=None) -> str:
    """Human-readable resolved table: one row per group with its leaf/param
    counts, transport plan (and degradation reason, if any), downlink plan
    and per-message wire words — what build/train/session print so a
    mixed-schedule run is legible in logs."""
    idx = _group_indices(schedule, tree)
    leaves = _leaves(tree)
    up_per, up_total = wire_words_tree(schedule, method, tree, "up", eta)
    dn_per, dn_total = wire_words_tree(schedule, method, tree, "down", eta)
    rows = [f"{'group':18s} {'leaves':>6s} {'params':>10s} "
            f"{'compressor':14s} {'carrier':12s} {'plan':10s} "
            f"{'down':8s} {'wire_up':>10s} {'wire_down':>10s}"]
    for gi, grp in enumerate(schedule.groups):
        m_g = group_method(method, grp)
        car = carrier_lib.make(grp.carrier)
        plan, reason = car.plan_with_reason(m_g, eta)
        params = sum(int(leaves[i].size) for i in idx[gi])
        rows.append(
            f"{grp.pattern:18s} {len(idx[gi]):6d} {params:10d} "
            f"{type(grp.compressor).__name__:14s} {grp.carrier:12s} "
            f"{plan:10s} {grp.down_carrier:8s} {up_per[gi]:10.0f} "
            f"{dn_per[gi]:10.0f}"
            + (f"  (degraded: {reason})" if reason else ""))
    rows.append(f"{'TOTAL':18s} {len(leaves):6d} "
                f"{sum(int(x.size) for x in leaves):10d} "
                f"{'':14s} {'':12s} {'':10s} {'':8s} {up_total:10.0f} "
                f"{dn_total:10.0f}")
    return "\n".join(rows)
