"""RunSpec: the single declarative, serializable name of one experiment.

The paper's point is that one small knob (Polyak momentum on EF21) changes the
complexity class — so the core workload of this repo is sweeping the
(method × compressor × carrier × mesh × arch) grid. A ``RunSpec`` names one
cell of that grid *completely*: arch + input geometry, mesh + ShardPlan,
method/compressor/carrier (η, ratio, state dtype), optimizer + lr, data
config, checkpoint policy, and seed. Every driver (launch/train.py,
launch/serve.py, launch/dryrun.py), example, and benchmark constructs a
RunSpec and hands it to :class:`repro.launch.session.Session` — there is no
other assembly path.

Design constraints:

* **Import-light.** This module imports NO jax (and configs/base.py, the arch
  registry it reads, stays jax-free too), so sweep tooling can emit spec
  files via ``python -m repro.launch.spec --print`` without paying a jax
  import, and the validation below runs in any process. The name/flag
  universes that logically live in jax-importing registries (methods,
  compressors, carriers, optimizers, mesh geometry, carrier degradation
  rules) are mirrored here as pure data; ``tests/test_spec.py`` asserts the
  mirrors equal the registries, so drift fails tier-1 loudly.
* **Fail at construction, not mid-driver.** ``__post_init__`` validates every
  field, including the carrier execution plan: a ``--carrier fused`` spec
  whose (method, compressor) would silently degrade to the unfused dense
  plan is a ``ValueError`` the moment the spec exists (mirroring the
  ``plan_with_reason`` hard error in launch/build.py, which still runs as the
  authoritative check when the EFConfig is built).
* **Stable serialization.** ``to_json``/``from_json`` round-trip exactly
  (``RunSpec.from_json(s.to_json()) == s``). The schema is versioned
  (``SCHEMA_VERSION``) and ``from_json`` REJECTS unknown keys — a spec
  written by a newer schema never silently drops experiment-defining fields.
  New fields must ship with defaults (additive evolution); renames/removals
  bump ``SCHEMA_VERSION``. ``results/specs/*.json`` holds golden fixtures
  that fail tier-1 on any drift.
* **Checkpoint compatibility.** ``spec_hash()`` hashes the canonical JSON of
  every experiment-defining field (checkpoint *policy* — ckpt_dir/ckpt_every
  — is excluded, so moving a checkpoint directory never invalidates it).
  ``Session.save`` embeds spec + hash in checkpoint meta; ``Session.resume``
  refuses a checkpoint written under a different hash unless overridden.

See DESIGN.md §7.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
from typing import Any, Dict, List, Optional, Tuple

from repro.configs import base as cb

# v2: bidirectional compression — the downlink_carrier / downlink_ratio
# fields change what a spec EXECUTES (a second compressed leg per round), so
# the bump made pre-downlink readers reject v2 specs loudly instead of
# silently running unidirectional rounds against a bidirectional definition.
# v3: per-parameter-group compression schedules — the ``groups`` field
# partitions the param pytree into named groups, each with its own
# (compressor × carrier × ratio × downlink × EF-state dtype). v2 specs are
# AUTO-UPGRADED on read: an absent ``groups`` IS the uniform one-group
# schedule derived from the single-knob fields, so every v2 spec names the
# same experiment it always named (and hashes identically — groups=[] is
# the default, excluded from the sparse spec_hash). One deliberate
# execution change rides the same release, independent of the schema: the
# BlockTopK sub-block geometry fix (compressors.py::BlockTopK.geom) gives
# leaves smaller than one block a proportional K instead of the degenerate
# full-block K, so a resumed v2 checkpoint whose model has sub-block
# leaves continues under the corrected compression, not the old bug.
# v4: partial participation — the ``participation`` field selects which
# clients upload each round ({mode: full|sampled|async, fraction, seed},
# DESIGN.md §11). v3 specs are AUTO-UPGRADED on read: an absent
# ``participation`` IS mode='full' (every client, every round — exactly what
# every v3 spec always executed), and the empty dict is the default, excluded
# from the sparse spec_hash, so v3 checkpoints stay resumable. v2 chains
# through the v3 upgrade first.
# v5: two-tier hierarchical aggregation — the ``hops`` field selects the
# pod topology ({pods, cross_carrier, cross_ratio}, DESIGN.md §13): clients
# → pod aggregator → global server, the cross-pod hop on its own carrier.
# v4 specs are AUTO-UPGRADED on read: an absent ``hops`` IS the flat
# topology (pods=1, zero hierarchical machinery — exactly what every v4
# spec always executed), and the empty dict is the default, excluded from
# the sparse spec_hash, so v4 checkpoints stay resumable (byte-stable
# hashes). v2/v3 chain through the earlier upgrades first.
SCHEMA_VERSION = 5

# ---------------------------------------------------------------------------
# jax-free mirrors of the jax-importing registries (sync-tested in
# tests/test_spec.py::test_name_universes_match_registries)
# ---------------------------------------------------------------------------

METHODS = frozenset({
    "ef21_sgdm_ideal", "ef21_sgd", "ef21_sgdm", "ef21_sgd2m", "ef21_sgdm_abs",
    "ef21_storm", "ef14_sgd", "sgdm", "sgd", "neolithic",
})
COMPRESSORS = frozenset({
    "identity", "topk", "randk", "block_topk", "hard_threshold", "natural",
    "rank1", "block_quant",
})
CARRIERS = frozenset({"dense", "sparse", "fused", "quant8", "quant4",
                      "fused_quant8", "fused_quant4"})
# the downlink broadcast has no fused path (the fused kernel IS the uplink
# client update) — naming it is a construction error, mirroring the carrier's
# own plan_down_with_reason degradation
DOWN_CARRIERS = frozenset(CARRIERS - {"fused"})
OPTIMIZERS = frozenset({"sgd", "adamw"})

MESHES = ("smoke", "pod", "multi_pod", "host4")
# geometry mirror of launch/mesh.py (PROD_DATA/PROD_MODEL/PROD_PODS, HOST4)
MESH_GEOM: Dict[str, Dict[str, int]] = {
    "smoke": {"data": 1, "model": 1},
    "pod": {"data": 16, "model": 16},
    "multi_pod": {"pod": 2, "data": 16, "model": 16},
    "host4": {"data": 4, "model": 1},
}

GRANULARITIES = ("group", "pod")
STATE_SHARDINGS = ("client", "zero")
EF_STATE_DTYPES = (None, "bfloat16")
MOE_IMPLS = ("dispatch", "dense")

# per-group schedule surface (mirror of core/schedule.py, sync-tested):
# the keys one ``groups`` entry may carry, the per-group EF-state dtype
# universe ('float32' exists so one group can force full precision under a
# bfloat16 spec-level default), and the characters the --schedule grammar
# reserves (a pattern containing one could never round-trip)
GROUP_KEYS = frozenset({"pattern", "carrier", "compressor", "ratio",
                        "compressor_kw", "downlink_carrier", "downlink_ratio",
                        "ef_state_dtype", "cross_carrier", "cross_ratio"})
GROUP_STATE_DTYPES = (None, "bfloat16", "float32")
PATTERN_RESERVED = set("=,:@")

# partial participation surface (mirror of core/participation.py,
# sync-tested): the modes a spec may name and the keys one ``participation``
# dict may carry. 'full' is the legacy every-client barrier; 'sampled' runs
# the masked-cohort synchronous path; 'async' names the event-driven
# simulator (core/participation.py::run_async) and is a construction error
# on the synchronous runtimes (launch/build.py).
PART_MODES = ("full", "sampled", "async")
PART_KEYS = frozenset({"mode", "fraction", "seed"})

# two-tier hierarchical aggregation surface (mirror of core/hierarchy.py,
# sync-tested): the keys a ``hops`` dict may carry. The cross-pod hop is one
# message per pod integrated like a broadcast, so its carrier universe is
# the downlink's (no fused — the fused kernel is the uplink client update).
HOP_KEYS = frozenset({"pods", "cross_carrier", "cross_ratio"})
CROSS_CARRIERS = DOWN_CARRIERS


def pattern_token_errors(pattern: str) -> List[str]:
    """Jax-free mirror of ``core.schedule.pattern_token_errors`` (sync-tested
    in tests/test_schedule.py): an empty ``|`` token matches every leaf, and
    an embedded ``'*'`` token would shadow every later group."""
    toks = pattern.split("|")
    errs = []
    if any(not t for t in toks):
        errs.append("empty '|' token (matches every leaf)")
    if "*" in toks and pattern != "*":
        errs.append("'*' may only be the standalone catch-all pattern")
    return errs

# methods with an ``eta`` field — the spec's eta drives ALL of them (a spec
# that records η=0.3 must never run a class default instead; method_kw can
# still override). Mirror of {cls has 'eta' field} — sync-tested.
ETA_METHODS = frozenset({"ef21_sgdm", "ef21_sgd2m", "sgdm", "ef21_storm",
                         "ef21_sgdm_abs", "ef21_sgdm_ideal"})

# attribute mirrors used by plan_preview (sync-tested against
# Method.wire_is_msg / Compressor.needs_rng / the carriers' own support sets)
WIRE_IS_NOT_MSG = frozenset({"ef21_sgdm_ideal", "ef21_sgdm_abs", "neolithic"})
NEEDS_RNG = frozenset({"randk", "natural"})
SPARSE_WIRE_OK = frozenset({"topk", "block_topk"})
FUSED_METHODS = frozenset({"ef21_sgdm", "ef21_sgd"})
FUSED_COMPRESSORS = frozenset({"block_topk"})


def plan_preview(method: str, compressor: str, carrier: str,
                 block: Optional[int] = None) -> Tuple[str, str]:
    """Pure-python mirror of ``Carrier.plan_with_reason`` (core/carriers.py)
    by name: (plan, reason) where plan ∈ {'dense','wire','fused',
    'fused_wire'} and reason is non-empty iff the carrier degraded to a
    less-fused plan (dense for sparse/quant/fused; the unfused quantized
    wire for fused_quant). η is always a static float in a RunSpec, so the
    traced-η degradations can never trigger here. ``block`` is the BlockTopK
    block width when the spec sets one (fused_quant4's uint4 packing needs
    it even; None = the even default). The plan (and reason emptiness) is
    asserted equal to the real carriers over the whole
    (method × compressor × carrier) grid in tests/test_spec.py."""
    if carrier == "dense":
        return "dense", ""
    if method in WIRE_IS_NOT_MSG:
        return "dense", (
            f"method {method!r} transmits a transform of c "
            "(wire_is_msg=False); a non-dense wire cannot ship it")
    if carrier == "sparse":
        if compressor not in SPARSE_WIRE_OK:
            return "dense", (
                f"compressor {compressor!r} has no deterministic fixed-size "
                "(values, indices) wire")
        return "wire", ""
    if carrier == "fused":
        if method not in FUSED_METHODS:
            return "dense", ("the fused kernel implements the EF21-SGD(M) "
                             f"client chain only, not {method!r}")
        if compressor not in FUSED_COMPRESSORS:
            return "dense", ("the fused kernel compresses with BlockTopK "
                             f"only, not {compressor!r}")
        return "fused", ""
    # quant8 / quant4 / fused_quant8 / fused_quant4
    if compressor in NEEDS_RNG:
        return "dense", (
            f"compressor {compressor!r} draws randomness inside encode; the "
            "quantized wire ships deterministic compressors only")
    if carrier in ("fused_quant8", "fused_quant4"):
        if method not in FUSED_METHODS:
            return "wire", (
                "the fused wire kernel implements the EF21-SGD(M) client "
                f"chain only, not {method!r}; running the unfused quantized "
                "wire")
        if compressor not in FUSED_COMPRESSORS:
            return "wire", (
                "the fused wire kernel compresses with BlockTopK only, not "
                f"{compressor!r}; running the unfused quantized wire")
        if carrier == "fused_quant4" and block is not None and block % 2:
            return "wire", (
                "uint4 packing needs an even BlockTopK block; running the "
                "unfused quantized wire")
        return "fused_wire", ""
    return "wire", ""


def downlink_plan_preview(compressor: str, carrier: str) -> Tuple[str, str]:
    """Pure-python mirror of ``Carrier.plan_down_with_reason``
    (core/carriers.py) by name: the DOWNLINK broadcast plan. No method enters
    — the broadcast payload is always the compressed innovation C(g − h), so
    only the compressor gates the wire. Asserted equal to the real carriers
    over the (compressor × carrier) grid in tests/test_spec.py."""
    if carrier == "dense":
        return "dense", ""
    if carrier == "fused":
        return "dense", (
            "the fused kernel fuses the UPLINK client update; the downlink "
            "broadcast has no fused path — use dense, sparse or quant")
    if carrier == "sparse":
        if compressor not in SPARSE_WIRE_OK:
            return "dense", (
                f"compressor {compressor!r} has no deterministic fixed-size "
                "(values, indices) wire")
        return "wire", ""
    # quant8 / quant4
    if compressor in NEEDS_RNG:
        return "dense", (
            f"compressor {compressor!r} draws randomness inside encode; the "
            "quantized wire ships deterministic compressors only")
    return "wire", ""


# ---------------------------------------------------------------------------
# per-group schedule: jax-free grammar + previews (DESIGN.md §9)
# ---------------------------------------------------------------------------

def parse_schedule_flag(s: str) -> List[Dict[str, Any]]:
    """Parse the ``--schedule`` value into a ``groups`` list. Two forms:

      grammar   ``"embed=dense,norm|bias=dense,*=quant4:0.05"`` — comma-
                separated ``pattern=carrier[:ratio][@compressor]`` entries
                (``dense`` with no ``@compressor`` means ship-uncompressed,
                i.e. the identity compressor; other carriers default to the
                spec's compressor at the given ratio)
      JSON      a ``[...]`` list of group dicts, for per-group knobs the
                grammar cannot express (downlink, state dtype, compressor_kw)

    ``format_schedule_flag`` is the inverse; grammar-expressible schedules
    round-trip exactly (tier-1 tested)."""
    if s.lstrip().startswith("["):
        return json.loads(s)
    out: List[Dict[str, Any]] = []
    for part in s.split(","):
        part = part.strip()
        pattern, sep, rhs = part.partition("=")
        if not sep or not pattern or not rhs:
            raise ValueError(
                f"bad --schedule entry {part!r}: want "
                "'pattern=carrier[:ratio][@compressor]'")
        comp = None
        if "@" in rhs:
            rhs, comp = rhs.split("@", 1)
        carrier, sep, ratio = rhs.partition(":")
        entry: Dict[str, Any] = {"pattern": pattern, "carrier": carrier}
        if sep:
            entry["ratio"] = float(ratio)
        if comp is not None:
            entry["compressor"] = comp
        out.append(entry)
    return out


def format_schedule_flag(groups: List[Dict[str, Any]]) -> str:
    """The canonical ``--schedule`` value for a ``groups`` list: the compact
    grammar when every entry is grammar-expressible, JSON otherwise."""
    parts = []
    for e in groups:
        if not ({"pattern", "carrier"} <= set(e)
                and set(e) <= {"pattern", "carrier", "ratio", "compressor"}):
            return json.dumps(groups, sort_keys=True)
        s = f"{e['pattern']}={e['carrier']}"
        if "ratio" in e:
            s += f":{e['ratio']}"
        if "compressor" in e:
            s += f"@{e['compressor']}"
        parts.append(s)
    return ",".join(parts)


def resolved_groups(spec: "RunSpec") -> List[Dict[str, Any]]:
    """The spec's schedule with every per-group default filled in. An empty
    ``groups`` IS the uniform one-group schedule of the single-knob fields
    (the v2 auto-upgrade); explicit entries default each absent key from the
    spec — except ``compressor``, which defaults to ``identity`` for a
    ``dense``-carrier group (ship-uncompressed, the grammar's reading of
    ``norm=dense``) and to the spec's compressor otherwise, and
    ``compressor_kw``, which only carries over when the group runs the
    spec's own compressor class."""
    # per-group cross-hop defaults come from the spec's hops (--hops sets
    # the uniform cross; a group entry overrides it for its own leaves)
    hop_car = spec.hops.get("cross_carrier", "dense") \
        if isinstance(spec.hops, dict) else "dense"
    hop_ratio = spec.hops.get("cross_ratio", spec.ratio) \
        if isinstance(spec.hops, dict) else spec.ratio
    if not spec.groups:
        return [{"pattern": "*", "carrier": spec.carrier,
                 "compressor": spec.compressor, "ratio": spec.ratio,
                 "compressor_kw": dict(spec.compressor_kw),
                 "downlink_carrier": spec.downlink_carrier,
                 "downlink_ratio": spec.downlink_ratio,
                 "ef_state_dtype": spec.ef_state_dtype,
                 "cross_carrier": hop_car, "cross_ratio": hop_ratio}]
    out = []
    for e in spec.groups:
        carrier = e.get("carrier", "dense")
        comp = e.get("compressor",
                     "identity" if carrier == "dense" else spec.compressor)
        kw = e.get("compressor_kw",
                   dict(spec.compressor_kw) if comp == spec.compressor
                   else {})
        out.append({
            "pattern": e.get("pattern"),
            "carrier": carrier,
            "compressor": comp,
            "ratio": e.get("ratio", spec.ratio),
            "compressor_kw": kw,
            "downlink_carrier": e.get("downlink_carrier",
                                      spec.downlink_carrier),
            "downlink_ratio": e.get("downlink_ratio", spec.downlink_ratio),
            "ef_state_dtype": e.get("ef_state_dtype", spec.ef_state_dtype),
            "cross_carrier": e.get("cross_carrier", hop_car),
            "cross_ratio": e.get("cross_ratio", hop_ratio),
        })
    return out


def schedule_preview(spec: "RunSpec") -> List[Dict[str, Any]]:
    """Jax-free mirror of the resolved group table: one row per group with
    the uplink plan (``plan_preview``) and downlink plan
    (``downlink_plan_preview``) that would execute — sync-tested against the
    real carriers/schedule objects in tests/test_schedule.py. Leaf/param
    counts need the real param tree and live in
    ``Session.schedule_table()``."""
    rows = []
    for g in resolved_groups(spec):
        blk = g["compressor_kw"].get("block") \
            if isinstance(g.get("compressor_kw"), dict) else None
        plan, reason = plan_preview(spec.method, g["compressor"],
                                    g["carrier"],
                                    blk if isinstance(blk, int) else None)
        dplan, dreason = downlink_plan_preview(g["compressor"],
                                               g["downlink_carrier"])
        rows.append({**g, "plan": plan, "plan_reason": reason,
                     "downlink_plan": dplan, "downlink_reason": dreason})
    return rows


# ---------------------------------------------------------------------------
# partial participation: jax-free grammar + preview (DESIGN.md §11)
# ---------------------------------------------------------------------------

def parse_participation_flag(s: str) -> Dict[str, Any]:
    """Parse the ``--participation`` value into a participation dict. Two
    forms:

      grammar   ``"sampled:0.25:7"`` — colon-separated ``mode[:fraction
                [:seed]]`` (``"full"``, ``"sampled:0.25"``, …)
      JSON      a ``{...}`` dict, for exact round-trips of any keyset

    ``format_participation_flag`` is the inverse; grammar-expressible dicts
    round-trip exactly (tier-1 tested)."""
    if s.lstrip().startswith("{"):
        return json.loads(s)
    parts = s.split(":")
    if len(parts) > 3 or not parts[0]:
        raise ValueError(f"bad --participation value {s!r}: want "
                         "'mode[:fraction[:seed]]' or a JSON dict")
    out: Dict[str, Any] = {"mode": parts[0]}
    if len(parts) >= 2:
        out["fraction"] = float(parts[1])
    if len(parts) == 3:
        out["seed"] = int(parts[2])
    return out


def format_participation_flag(p: Dict[str, Any]) -> str:
    """The canonical ``--participation`` value for a participation dict: the
    compact grammar when the keyset is a grammar prefix, JSON otherwise."""
    keys = set(p)
    if keys == {"mode"}:
        return str(p["mode"])
    if keys == {"mode", "fraction"}:
        return f"{p['mode']}:{p['fraction']}"
    if keys == {"mode", "fraction", "seed"}:
        return f"{p['mode']}:{p['fraction']}:{p['seed']}"
    return json.dumps(p, sort_keys=True)


def participation_preview(spec: "RunSpec") -> Dict[str, Any]:
    """Jax-free resolved participation: mode/fraction/seed with defaults
    filled in, plus the paper's n for this spec and the per-round cohort
    size m = max(1, round(fraction·n)) — EXACTLY the arithmetic of
    ``core.participation.Participation.cohort_size`` (sync-tested in
    tests/test_participation_properties.py)."""
    p = spec.participation
    mode = p.get("mode", "full") if p else "full"
    fraction = float(p.get("fraction", 1.0)) if p else 1.0
    seed = int(p.get("seed", 0)) if p else 0
    n = spec.n_clients_preview()
    cohort = n if mode == "full" else max(1, int(round(fraction * n)))
    return {"mode": mode, "fraction": fraction, "seed": seed,
            "n": n, "cohort": cohort}


# ---------------------------------------------------------------------------
# two-tier hierarchical aggregation: jax-free grammar + preview (§13)
# ---------------------------------------------------------------------------

def parse_hops_flag(s: str) -> Dict[str, Any]:
    """Parse the ``--hops`` value into a hops dict. Two forms:

      grammar   ``"pods=2,cross=quant4:0.05"`` — comma-separated
                ``pods=<int>`` and ``cross=carrier[:ratio]`` entries
      JSON      a ``{...}`` dict, for exact round-trips of any keyset

    ``format_hops_flag`` is the inverse; grammar-expressible dicts
    round-trip exactly (tier-1 tested)."""
    if s.lstrip().startswith("{"):
        return json.loads(s)
    out: Dict[str, Any] = {}
    for part in s.split(","):
        part = part.strip()
        key, sep, rhs = part.partition("=")
        if not sep or not rhs:
            raise ValueError(f"bad --hops entry {part!r}: want "
                             "'pods=<int>' or 'cross=carrier[:ratio]'")
        if key == "pods":
            out["pods"] = int(rhs)
        elif key == "cross":
            carrier, sep, ratio = rhs.partition(":")
            out["cross_carrier"] = carrier
            if sep:
                out["cross_ratio"] = float(ratio)
        else:
            raise ValueError(f"bad --hops key {key!r}: want 'pods' or "
                             "'cross'")
    return out


def format_hops_flag(h: Dict[str, Any]) -> str:
    """The canonical ``--hops`` value for a hops dict: the compact grammar
    when the keyset is grammar-expressible, JSON otherwise."""
    if not set(h) <= HOP_KEYS:
        return json.dumps(h, sort_keys=True)
    parts = []
    if "pods" in h:
        parts.append(f"pods={h['pods']}")
    if "cross_carrier" in h:
        s = f"cross={h['cross_carrier']}"
        if "cross_ratio" in h:
            s += f":{h['cross_ratio']}"
        parts.append(s)
    elif "cross_ratio" in h:
        return json.dumps(h, sort_keys=True)
    return ",".join(parts)


def hops_preview(spec: "RunSpec") -> Dict[str, Any]:
    """Jax-free resolved hop topology: pods/cross carrier/ratio with
    defaults filled in, the per-pod client count, and the flat-equivalence
    predicate (``trivial_cross`` — a dense cross ships the exact pod target,
    so the round is bit-identical to the flat path). Mirrors
    ``core.hierarchy.Hops`` semantics exactly (sync-tested in
    tests/test_hierarchy.py)."""
    h = spec.hops
    pods = int(h.get("pods", 1)) if h else 1
    cross_carrier = h.get("cross_carrier", "dense") if h else "dense"
    cross_ratio = float(h.get("cross_ratio", spec.ratio)) if h else spec.ratio
    n = spec.n_clients_preview()
    return {"pods": pods, "cross_carrier": cross_carrier,
            "cross_ratio": cross_ratio, "n": n,
            "clients_per_pod": n // pods if pods and n % pods == 0 else None,
            "hierarchical": pods > 1,
            "trivial_cross": cross_carrier == "dense"}


def _known_arch(arch: str) -> bool:
    return arch in cb.ARCH_ALIASES or arch in cb.ARCH_IDS


_JSON_SCALARS = (str, int, float, bool, type(None))


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """The full, JSON-round-trippable name of one experiment. Frozen; every
    field is validated in ``__post_init__`` so an invalid spec never exists.

    ``shape`` selects a *named* production InputShape (configs/base.py) for
    ``Session.lower()`` / the dry-run; interactive training and serving use
    the explicit (``seq_len``, ``global_batch``) geometry. ``clients`` is the
    number of emulated EF clients on the single-device (smoke-mesh) path; on
    multi-device meshes n is derived from mesh × client_granularity exactly
    as DESIGN.md §3 maps clients onto data-parallel groups."""

    version: int = SCHEMA_VERSION

    # -- experiment identity -------------------------------------------------
    arch: str = "smollm-360m"
    smoke: bool = False                    # reduced per-arch config (CPU-sized)
    shape: Optional[str] = None            # named InputShape for lower()/dryrun
    seq_len: int = 256
    global_batch: int = 16

    # -- mesh / placement (ShardPlan) ----------------------------------------
    mesh: str = "smoke"                    # one of MESHES
    client_granularity: str = "group"      # ShardPlan: 'group' | 'pod'
    state_sharding: str = "client"         # ShardPlan: 'client' | 'zero'
    ef_state_dtype: Optional[str] = None   # ShardPlan: None | 'bfloat16'
    clients: int = 8                       # emulated clients on 1-device mesh

    # -- method / transport --------------------------------------------------
    method: str = "ef21_sgdm"
    compressor: str = "block_topk"
    ratio: float = 0.05
    eta: float = 0.1
    carrier: str = "dense"
    # downlink (server → client broadcast) leg, DESIGN.md §8: 'dense' = no
    # downlink machinery (the implicit dense broadcast — pre-v2 behavior,
    # bit-identical). Any other carrier adds the EF21 server memory h and
    # broadcasts C(g − h) as that carrier's wire; the downlink compressor is
    # the uplink compressor class re-budgeted to downlink_ratio
    # (launch/session.py::make_down_compressor).
    downlink_carrier: str = "dense"
    downlink_ratio: float = 0.05
    # per-parameter-group compression schedule (DESIGN.md §9): an ordered
    # list of group dicts (keys ⊆ GROUP_KEYS; 'pattern' mandatory, the last
    # entry must be the catch-all '*'), first-match-wins over the param
    # pytree's leaf paths. Empty = the uniform one-group schedule of the
    # single-knob fields above (the v2 meaning, bit-identical). Absent keys
    # default from the spec (resolved_groups); the --schedule flag grammar
    # is 'pattern=carrier[:ratio][@compressor],…' (parse_schedule_flag).
    groups: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # comm/compute overlap (DESIGN.md §10): gather-wire aggregations on the
    # shard_map runtime transport their all-gather as a ppermute ring and
    # decode each chunk while the next is in flight. Bit-identical to the
    # blocking anchor; a no-op for all-reduce wires and the vmap runtimes.
    overlap: bool = False
    # partial participation (DESIGN.md §11): which clients upload each round.
    # Empty dict = mode 'full' (every client, every round — the v3 meaning,
    # bit-identical, excluded from the sparse spec_hash). mode='sampled'
    # draws a seeded cohort of max(1, round(fraction·n)) clients per round
    # (--participation sampled:0.25:7); fraction=1.0 sampling is
    # bit-identical to 'full' (tests/test_participation.py). mode='async'
    # names the event-driven simulator and never runs the synchronous
    # drivers. Keys ⊆ PART_KEYS.
    participation: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # two-tier hierarchical aggregation (DESIGN.md §13): clients → pod
    # aggregator → global server, the cross-pod hop on its own carrier.
    # Empty dict = the flat topology (pods=1, zero hierarchical machinery —
    # the v4 meaning, bit-identical, excluded from the sparse spec_hash).
    # {"pods": 2, "cross_carrier": "quant4", "cross_ratio": 0.05} keeps the
    # intra-pod hop on the spec's carrier/schedule and ships one quant4
    # innovation per pod across the slow links, error-fed by a per-pod EF
    # memory (--hops pods=2,cross=quant4:0.05). Keys ⊆ HOP_KEYS. The cross
    # compressor is the uplink compressor class re-budgeted to cross_ratio
    # (launch/session.py::make_hops), exactly like the downlink's.
    hops: Dict[str, Any] = dataclasses.field(default_factory=dict)
    method_kw: Dict[str, Any] = dataclasses.field(default_factory=dict)
    compressor_kw: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- arch overrides (dry-run sweep knobs, applied to the ArchConfig) -----
    tp_pad_heads: int = 0
    moe_impl: str = "dispatch"

    # -- optimizer -----------------------------------------------------------
    optimizer: str = "sgd"
    lr: float = 0.5

    # -- data ----------------------------------------------------------------
    heterogeneity: float = 0.5
    seed: int = 0

    # -- checkpoint policy (excluded from spec_hash) -------------------------
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0                    # 0 → save only at end of train()

    # ------------------------------------------------------------------ valid
    def __post_init__(self):
        errs: List[str] = []
        if self.version != SCHEMA_VERSION:
            errs.append(f"schema version {self.version} unsupported "
                        f"(this build reads v{SCHEMA_VERSION})")
        if not _known_arch(self.arch):
            errs.append(f"unknown arch {self.arch!r}; have "
                        f"{sorted(cb.ARCH_ALIASES)}")
        if self.shape is not None and self.shape not in cb.INPUT_SHAPES:
            errs.append(f"unknown shape {self.shape!r}; have "
                        f"{sorted(cb.INPUT_SHAPES)}")
        if self.mesh not in MESHES:
            errs.append(f"unknown mesh {self.mesh!r}; have {list(MESHES)}")
        for field, val, allowed in [
                ("client_granularity", self.client_granularity, GRANULARITIES),
                ("state_sharding", self.state_sharding, STATE_SHARDINGS),
                ("ef_state_dtype", self.ef_state_dtype, EF_STATE_DTYPES),
                ("moe_impl", self.moe_impl, MOE_IMPLS)]:
            if val not in allowed:
                errs.append(f"{field}={val!r} not in {list(allowed)}")
        for field, val, universe in [
                ("method", self.method, METHODS),
                ("compressor", self.compressor, COMPRESSORS),
                ("carrier", self.carrier, CARRIERS),
                ("downlink_carrier", self.downlink_carrier, DOWN_CARRIERS),
                ("optimizer", self.optimizer, OPTIMIZERS)]:
            if val not in universe:
                errs.append(f"unknown {field} {val!r}; have {sorted(universe)}")
        if self.seq_len <= 0:
            errs.append(f"seq_len must be positive, got {self.seq_len}")
        if self.global_batch <= 0:
            errs.append(f"global_batch must be positive, got "
                        f"{self.global_batch}")
        if self.clients < 1:
            errs.append(f"clients must be >= 1, got {self.clients}")
        if not 0.0 < self.eta <= 1.0:
            errs.append(f"eta must be in (0, 1], got {self.eta}")
        if not 0.0 < self.ratio <= 1.0:
            errs.append(f"ratio must be in (0, 1], got {self.ratio}")
        if not 0.0 < self.downlink_ratio <= 1.0:
            errs.append(f"downlink_ratio must be in (0, 1], got "
                        f"{self.downlink_ratio}")
        if not 0.0 <= self.heterogeneity <= 1.0:
            errs.append(f"heterogeneity must be in [0, 1], got "
                        f"{self.heterogeneity}")
        if self.tp_pad_heads < 0:
            errs.append(f"tp_pad_heads must be >= 0, got {self.tp_pad_heads}")
        if self.ckpt_every < 0:
            errs.append(f"ckpt_every must be >= 0, got {self.ckpt_every}")
        for kw_name, kw in [("method_kw", self.method_kw),
                            ("compressor_kw", self.compressor_kw)]:
            if not isinstance(kw, dict) or not all(
                    isinstance(k, str) and isinstance(v, _JSON_SCALARS)
                    for k, v in kw.items()):
                errs.append(f"{kw_name} must map str keys to JSON scalars, "
                            f"got {kw!r}")
        errs.extend(self._validate_groups())
        errs.extend(self._validate_participation())
        errs.extend(self._validate_hops())
        # the (batch % clients) divisibility the runtime would assert
        # mid-step — checked for BOTH batch geometries a spec can run: the
        # interactive train geometry (global_batch, Session.train) and,
        # when set, the named dry-run shape (Session.lower)
        shape_ok = self.shape is None or self.shape in cb.INPUT_SHAPES
        n = self.n_clients_preview() if self.mesh in MESHES else 1
        batches = [self.global_batch]
        if shape_ok and self.shape is not None \
                and self.train_kind() == "train":
            batches.append(self.train_batch())
        for batch in batches:
            if batch > 0 and n >= 1 and batch % n != 0:
                errs.append(f"global batch {batch} not divisible by the "
                            f"{n} EF clients of mesh={self.mesh!r} "
                            f"granularity={self.client_granularity!r}")
        # the fused-misconfig hard error, at construction time (the same check
        # runs authoritatively against the real carrier in launch/build.py)
        if self.carrier in CARRIERS and self.method in METHODS \
                and self.compressor in COMPRESSORS:
            plan, reason = self.plan()
            if self.carrier == "fused" and plan != "fused":
                errs.append(
                    "carrier='fused' would silently run the UNFUSED dense "
                    f"plan: {reason}. Pick carrier='dense' or 'sparse' for "
                    f"method={self.method!r} compressor={self.compressor!r}")
            if self.carrier in ("fused_quant8", "fused_quant4") \
                    and plan != "fused_wire":
                errs.append(
                    f"carrier={self.carrier!r} would silently run a "
                    f"DEGRADED plan ({plan!r}): {reason}. Pick "
                    "carrier='quant8'/'quant4' (the unfused quantized wire) "
                    f"for method={self.method!r} "
                    f"compressor={self.compressor!r}")
        if errs:
            raise ValueError("invalid RunSpec:\n  - " + "\n  - ".join(errs))

    def _validate_groups(self) -> List[str]:
        """Construction-time schedule validation, jax-free (the real
        CompressionSchedule re-validates authoritatively in
        session.make_schedule)."""
        errs: List[str] = []
        if not isinstance(self.groups, list):
            return [f"groups must be a list of dicts, got {self.groups!r}"]
        if not self.groups:
            return errs
        seen = set()
        for i, e in enumerate(self.groups):
            if not isinstance(e, dict):
                errs.append(f"groups[{i}] must be a dict, got {e!r}")
                continue
            unknown = sorted(set(e) - GROUP_KEYS)
            if unknown:
                errs.append(f"groups[{i}]: unknown keys {unknown}; have "
                            f"{sorted(GROUP_KEYS)}")
            pat = e.get("pattern")
            if not pat or not isinstance(pat, str):
                errs.append(f"groups[{i}] needs a non-empty 'pattern'")
                continue
            bad = PATTERN_RESERVED & set(pat)
            if bad:
                errs.append(f"groups[{i}] pattern {pat!r} uses reserved "
                            f"characters {sorted(bad)}")
            errs.extend(f"groups[{i}] pattern {pat!r}: {e}"
                        for e in pattern_token_errors(pat))
            if pat in seen:
                errs.append(f"duplicate group pattern {pat!r}")
            seen.add(pat)
            if pat == "*" and i != len(self.groups) - 1:
                errs.append("the catch-all '*' must be the LAST group "
                            "(first-match-wins shadows everything after it)")
            carrier = e.get("carrier", "dense")
            if carrier not in CARRIERS:
                errs.append(f"groups[{i}]: unknown carrier {carrier!r}")
                continue
            comp = e.get("compressor",
                         "identity" if carrier == "dense"
                         else self.compressor)
            if comp not in COMPRESSORS:
                errs.append(f"groups[{i}]: unknown compressor {comp!r}")
                continue
            if e.get("downlink_carrier", "dense") not in DOWN_CARRIERS:
                errs.append(f"groups[{i}]: downlink carrier "
                            f"{e['downlink_carrier']!r} not in "
                            f"{sorted(DOWN_CARRIERS)}")
            if e.get("cross_carrier", "dense") not in CROSS_CARRIERS:
                errs.append(f"groups[{i}]: cross carrier "
                            f"{e['cross_carrier']!r} not in "
                            f"{sorted(CROSS_CARRIERS)}")
            if e.get("ef_state_dtype") not in GROUP_STATE_DTYPES:
                errs.append(f"groups[{i}]: ef_state_dtype "
                            f"{e['ef_state_dtype']!r} not in "
                            f"{list(GROUP_STATE_DTYPES)}")
            for key in ("ratio", "downlink_ratio", "cross_ratio"):
                if key in e and not (isinstance(e[key], (int, float))
                                     and 0.0 < e[key] <= 1.0):
                    errs.append(f"groups[{i}]: {key} must be in (0, 1], "
                                f"got {e[key]!r}")
            kw = e.get("compressor_kw", {})
            if not isinstance(kw, dict) or not all(
                    isinstance(k, str) and isinstance(v, _JSON_SCALARS)
                    for k, v in kw.items()):
                errs.append(f"groups[{i}]: compressor_kw must map str keys "
                            f"to JSON scalars, got {kw!r}")
            # the fused-misconfig hard error, per group (mirrors the
            # authoritative per-group check in launch/build.py)
            if self.method in METHODS:
                blk = kw.get("block") if isinstance(kw, dict) else None
                plan, reason = plan_preview(
                    self.method, comp, carrier,
                    blk if isinstance(blk, int) else None)
                if carrier == "fused" and plan != "fused":
                    errs.append(
                        f"groups[{i}] ({pat!r}): carrier='fused' would "
                        f"silently run the UNFUSED dense plan: {reason}")
                if carrier in ("fused_quant8", "fused_quant4") \
                        and plan != "fused_wire":
                    errs.append(
                        f"groups[{i}] ({pat!r}): carrier={carrier!r} would "
                        f"silently run a DEGRADED plan ({plan!r}): {reason}")
        # reported alongside any per-entry errors (one fix-and-rerun pass,
        # like the authoritative CompressionSchedule.__post_init__)
        if isinstance(self.groups[-1], dict) \
                and self.groups[-1].get("pattern") != "*":
            errs.append("the last group must be the mandatory catch-all "
                        "'*' so every leaf lands in exactly one group")
        return errs

    def _validate_participation(self) -> List[str]:
        """Construction-time participation validation, jax-free (the real
        Participation re-validates authoritatively in
        session.make_participation / launch/build.py)."""
        p = self.participation
        if not isinstance(p, dict):
            return [f"participation must be a dict, got {p!r}"]
        if not p:
            return []
        errs: List[str] = []
        unknown = sorted(set(p) - PART_KEYS)
        if unknown:
            errs.append(f"participation: unknown keys {unknown}; have "
                        f"{sorted(PART_KEYS)}")
        mode = p.get("mode", "full")
        if mode not in PART_MODES:
            errs.append(f"participation: unknown mode {mode!r}; have "
                        f"{list(PART_MODES)}")
        frac = p.get("fraction", 1.0)
        if not (isinstance(frac, (int, float)) and not isinstance(frac, bool)
                and 0.0 < frac <= 1.0):
            errs.append(f"participation: fraction must be in (0, 1], got "
                        f"{frac!r}")
        seed = p.get("seed", 0)
        if not isinstance(seed, int) or isinstance(seed, bool):
            errs.append(f"participation: seed must be an int, got {seed!r}")
        if mode in ("sampled", "async"):
            # the fused wire aggregates INSIDE the mega-kernel — there is no
            # per-client wire left to mask, so a sampled cohort cannot ride
            # it. Fail at construction, like the fused-misconfig errors.
            fused_wire_carriers = {"fused_quant8", "fused_quant4"}
            bad = []
            if self.carrier in fused_wire_carriers:
                bad.append(f"carrier={self.carrier!r}")
            for i, e in enumerate(self.groups):
                if isinstance(e, dict) \
                        and e.get("carrier") in fused_wire_carriers:
                    bad.append(f"groups[{i}] "
                               f"(pattern={e.get('pattern')!r})")
            if bad:
                errs.append(
                    f"participation mode {mode!r} cannot run the fused "
                    f"quantized wire ({', '.join(bad)}): the mega-kernel "
                    "aggregates all clients inside, leaving no per-client "
                    "wire to mask — use carrier='quant8'/'quant4'")
        return errs

    def _validate_hops(self) -> List[str]:
        """Construction-time hop-topology validation, jax-free (the real
        Hops re-validates authoritatively in session.make_hops /
        launch/build.py)."""
        h = self.hops
        if not isinstance(h, dict):
            return [f"hops must be a dict, got {h!r}"]
        if not h:
            return []
        errs: List[str] = []
        unknown = sorted(set(h) - HOP_KEYS)
        if unknown:
            errs.append(f"hops: unknown keys {unknown}; have "
                        f"{sorted(HOP_KEYS)}")
        pods = h.get("pods", 1)
        if not isinstance(pods, int) or isinstance(pods, bool) or pods < 1:
            errs.append(f"hops: pods must be an int >= 1, got {pods!r}")
            return errs
        cross = h.get("cross_carrier", "dense")
        if cross not in CROSS_CARRIERS:
            errs.append(f"hops: unknown cross carrier {cross!r}; have "
                        f"{sorted(CROSS_CARRIERS)}")
        ratio = h.get("cross_ratio", self.ratio)
        if not (isinstance(ratio, (int, float))
                and not isinstance(ratio, bool) and 0.0 < ratio <= 1.0):
            errs.append(f"hops: cross_ratio must be in (0, 1], got {ratio!r}")
        if pods == 1:
            return errs
        # pods > 1: the topology constraints
        n = self.n_clients_preview() if self.mesh in MESHES else pods
        if n % pods != 0:
            errs.append(f"hops: pods={pods} must divide the {n} EF clients "
                        f"of mesh={self.mesh!r} "
                        f"granularity={self.client_granularity!r}")
        if self.mesh in ("pod", "host4"):
            errs.append(f"hops: mesh={self.mesh!r} has no pod axis — "
                        "hierarchical aggregation (pods > 1) needs "
                        "mesh='multi_pod' or "
                        "the single-device smoke mesh (vmap emulation)")
        if self.mesh == "multi_pod" \
                and pods != MESH_GEOM["multi_pod"]["pod"]:
            errs.append(f"hops: pods={pods} must equal the multi_pod mesh's "
                        f"pod axis ({MESH_GEOM['multi_pod']['pod']})")
        if self.client_granularity == "pod":
            errs.append("hops: client_granularity='pod' makes each pod ONE "
                        "client — there is no intra-pod hop left to "
                        "aggregate; use granularity='group'")
        mode = self.participation.get("mode", "full") \
            if isinstance(self.participation, dict) else "full"
        if mode in ("sampled", "async"):
            errs.append(
                f"hops: participation mode {mode!r} does not compose with "
                "hierarchical aggregation (a partial cohort breaks the "
                "pod-major client blocks) — use mode='full'")
        # the fused wire aggregates all clients inside the mega-kernel:
        # there is no per-pod message left to re-aggregate
        fused_wire_carriers = {"fused_quant8", "fused_quant4"}
        bad = []
        if self.carrier in fused_wire_carriers:
            bad.append(f"carrier={self.carrier!r}")
        for i, e in enumerate(self.groups):
            if isinstance(e, dict) \
                    and e.get("carrier") in fused_wire_carriers:
                bad.append(f"groups[{i}] (pattern={e.get('pattern')!r})")
        if bad:
            errs.append(
                f"hops: hierarchical aggregation cannot run the fused "
                f"quantized wire ({', '.join(bad)}): the mega-kernel "
                "aggregates all clients inside, leaving no per-pod message "
                "— use carrier='quant8'/'quant4'")
        return errs

    # -------------------------------------------------------------- previews
    def plan(self) -> Tuple[str, str]:
        """(execution plan, degradation reason) for this spec's carrier —
        see plan_preview."""
        block = self.compressor_kw.get("block") \
            if isinstance(self.compressor_kw, dict) else None
        return plan_preview(self.method, self.compressor, self.carrier,
                            block if isinstance(block, int) else None)

    def downlink_plan(self) -> Tuple[str, str]:
        """(execution plan, degradation reason) for the downlink broadcast —
        see downlink_plan_preview."""
        return downlink_plan_preview(self.compressor, self.downlink_carrier)

    def train_kind(self) -> str:
        """'train' | 'prefill' | 'decode' of the named shape (custom
        geometry is always a train shape)."""
        if self.shape is not None:
            return cb.INPUT_SHAPES[self.shape].kind
        return "train"

    def train_batch(self) -> int:
        if self.shape is not None:
            return cb.INPUT_SHAPES[self.shape].global_batch
        return self.global_batch

    def n_clients_preview(self) -> int:
        """The paper's n for this spec, computable without jax: the emulated
        client count on the 1-device smoke mesh, else derived from mesh
        geometry × client granularity (DESIGN.md §3)."""
        if self.mesh == "smoke":
            return self.clients
        geom = MESH_GEOM[self.mesh]
        if self.client_granularity == "pod":
            return geom.get("pod", 1)
        n = 1
        for ax in ("pod", "data"):
            n *= geom.get(ax, 1)
        return n

    # --------------------------------------------------------- serialization
    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), sort_keys=True, indent=indent)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RunSpec":
        if "version" not in d:
            raise ValueError("spec dict has no 'version' key — refusing to "
                             "guess the schema")
        # v2 → v3 → v4 → v5 chained auto-upgrade: each bump is purely
        # additive (v3's ``groups`` defaults to the uniform one-group
        # schedule of the single-knob fields; v4's ``participation``
        # defaults to mode 'full'; v5's ``hops`` defaults to the flat
        # topology — exactly what every older spec always executed), so old
        # dicts upgrade mechanically and round-trip at the current schema.
        # v1 (pre-downlink) stays rejected: its absence of downlink fields
        # changed execution.
        if d.get("version") == 2 and "groups" not in d:
            d = dict(d, version=3)
        if d.get("version") == 3 and "participation" not in d:
            d = dict(d, version=4)
        if d.get("version") == 4 and "hops" not in d:
            d = dict(d, version=SCHEMA_VERSION)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(
                f"unknown RunSpec keys {unknown} (schema v{d['version']}, "
                f"this build reads v{SCHEMA_VERSION}) — refusing to silently "
                "drop experiment-defining fields")
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "RunSpec":
        return cls.from_dict(json.loads(s))

    def spec_hash(self) -> str:
        """Hash of the experiment-defining fields, in SPARSE canonical form:
        only fields that differ from their defaults are hashed, and
        ``version`` / checkpoint policy (ckpt_dir, ckpt_every) are excluded.
        Consequences: moving a ckpt dir never invalidates a checkpoint, and
        the documented additive schema evolution (new field + default) keeps
        every existing checkpoint resumable — an old hash and a new one
        agree whenever the explicitly-set fields agree. The flip side is
        that changing a field's DEFAULT silently preserves hashes, so
        semantic default changes must bump SCHEMA_VERSION (which gates
        ``from_dict`` before any hash comparison happens)."""
        d = self.to_dict()
        base = dataclasses.asdict(_DEFAULT) if _DEFAULT is not None else {}
        sparse = {k: v for k, v in d.items()
                  if k not in ("version", "ckpt_dir", "ckpt_every")
                  and v != base.get(k)}
        return hashlib.sha256(
            json.dumps(sparse, sort_keys=True).encode()).hexdigest()[:16]

    def diff(self, other: "RunSpec") -> List[str]:
        """Human-readable list of differing fields (for resume refusals)."""
        out = []
        for f in dataclasses.fields(self):
            a, b = getattr(self, f.name), getattr(other, f.name)
            if a != b:
                out.append(f"{f.name}: {a!r} != {b!r}")
        return out

    # ------------------------------------------------------------------ flags
    def to_flags(self) -> List[str]:
        """CLI flags reconstructing this spec:
        ``RunSpec.from_flags(s.to_flags()) == s`` (tier-1 tested)."""
        default = _DEFAULT
        out: List[str] = []
        for flag, field, kind in _FLAGS:
            val = getattr(self, field)
            if val == getattr(default, field):
                continue
            if kind == "bool":
                if val:
                    out.append(flag)
            elif kind == "json":
                out.extend([flag, json.dumps(val, sort_keys=True)])
            elif kind == "schedule":
                out.extend([flag, format_schedule_flag(val)])
            elif kind == "participation":
                out.extend([flag, format_participation_flag(val)])
            elif kind == "hops":
                out.extend([flag, format_hops_flag(val)])
            else:
                out.extend([flag, str(val)])
        return out

    @classmethod
    def from_flags(cls, argv: Optional[List[str]] = None) -> "RunSpec":
        ap = argparse.ArgumentParser(add_help=False)
        add_flags(ap)
        return cls.from_args(ap.parse_args(argv))

    @classmethod
    def from_args(cls, args: argparse.Namespace) -> "RunSpec":
        """Build a spec from parsed flags. ``--spec FILE`` (when present in
        the namespace) loads a JSON spec as the base; explicitly passed flags
        override it (unset flags parse as None and never override)."""
        base = cls()
        spec_file = getattr(args, "spec_file", None)
        if spec_file:
            with open(spec_file) as f:
                base = cls.from_json(f.read())
        overrides = {}
        for _, field, kind in _FLAGS:
            val = getattr(args, field, None)
            if val is None:
                continue
            overrides[field] = val
        return dataclasses.replace(base, **overrides) if overrides else base


_DEFAULT = None  # set after class definition (RunSpec() self-validates)

# (flag, field, kind) — dest is always the field name, so argparse namespaces
# map 1:1 onto RunSpec fields
_FLAGS: List[Tuple[str, str, str]] = [
    ("--arch", "arch", "str"),
    ("--smoke", "smoke", "bool"),
    ("--shape", "shape", "str"),
    ("--seq", "seq_len", "int"),
    ("--global-batch", "global_batch", "int"),
    ("--mesh", "mesh", "str"),
    ("--granularity", "client_granularity", "str"),
    ("--state-sharding", "state_sharding", "str"),
    ("--ef-state-dtype", "ef_state_dtype", "str"),
    ("--clients", "clients", "int"),
    ("--method", "method", "str"),
    ("--compressor", "compressor", "str"),
    ("--ratio", "ratio", "float"),
    ("--eta", "eta", "float"),
    ("--carrier", "carrier", "str"),
    ("--downlink-carrier", "downlink_carrier", "str"),
    ("--downlink-ratio", "downlink_ratio", "float"),
    ("--schedule", "groups", "schedule"),
    ("--overlap", "overlap", "bool"),
    ("--participation", "participation", "participation"),
    ("--hops", "hops", "hops"),
    ("--method-kw", "method_kw", "json"),
    ("--compressor-kw", "compressor_kw", "json"),
    ("--tp-pad-heads", "tp_pad_heads", "int"),
    ("--moe-impl", "moe_impl", "str"),
    ("--optimizer", "optimizer", "str"),
    ("--lr", "lr", "float"),
    ("--heterogeneity", "heterogeneity", "float"),
    ("--seed", "seed", "int"),
    ("--ckpt-dir", "ckpt_dir", "str"),
    ("--ckpt-every", "ckpt_every", "int"),
]

_FLAG_HELP = {
    "--smoke": "reduced per-arch config (CPU-sized)",
    "--shape": "named production InputShape for lower()/dryrun",
    "--carrier": "wire carrier for the EF sync (core/carriers.py): dense "
                 "all-reduce, sparse (values,indices) all-gather, the fused "
                 "Pallas client update, block-quantized wires, or the "
                 "one-launch fused quantized wires (fused_quant8/4)",
    "--overlap": "comm/compute overlap (DESIGN.md §10): ring-transport "
                 "gather-wire aggregations on the shard_map runtime, "
                 "decoding each chunk while the next is in flight; "
                 "bit-identical to the blocking anchor",
    "--downlink-carrier": "wire carrier for the server → client broadcast "
                          "(DESIGN.md §8): 'dense' keeps the implicit dense "
                          "f32 broadcast; sparse/quant8/quant4 add the EF21 "
                          "server memory h and ship C(g − h) as that "
                          "carrier's wire",
    "--downlink-ratio": "compression budget of the downlink compressor (the "
                        "uplink compressor class, re-budgeted; like --ratio "
                        "it only applies to ratio-bearing compressors — "
                        "others reuse their compressor-kw budget unchanged)",
    "--schedule": "per-parameter-group compression schedule (DESIGN.md §9): "
                  "'pattern=carrier[:ratio][@compressor],…' entries matched "
                  "first-match-wins against param leaf paths, last must be "
                  "the catch-all '*' — e.g. "
                  "'norm|bias=dense,embed=quant4:0.05,*=sparse:0.02'; a JSON "
                  "[...] list unlocks per-group downlink / state-dtype knobs",
    "--participation": "partial participation (DESIGN.md §11): "
                       "'mode[:fraction[:seed]]' — 'full' (every client, "
                       "every round), 'sampled:0.25:7' (a seeded cohort of "
                       "max(1, round(fraction·n)) clients per round; "
                       "non-sampled clients' EF state stays frozen), or a "
                       "JSON {...} dict; 'async' names the event-driven "
                       "simulator (core/participation.py) and refuses the "
                       "synchronous drivers",
    "--hops": "two-tier hierarchical aggregation (DESIGN.md §13): "
              "'pods=<int>,cross=carrier[:ratio]' — clients aggregate over "
              "the fast intra-pod links on the spec's carrier/schedule, "
              "then each pod's aggregator error-feeds one compressed "
              "innovation per round across the slow cross-pod links, e.g. "
              "'pods=2,cross=quant4:0.05'; 'cross=dense' (or pods=1) is "
              "bit-identical to the flat path; a JSON {...} dict also "
              "round-trips",
    "--clients": "emulated EF clients on the single-device mesh",
    "--method-kw": "JSON dict of extra Method kwargs (e.g. "
                   "'{\"gamma\": 0.01}')",
    "--compressor-kw": "JSON dict of extra Compressor kwargs (e.g. "
                       "'{\"block\": 1024, \"k_per_block\": 16}')",
}

_FLAG_CHOICES = {
    "--shape": sorted(cb.INPUT_SHAPES),
    "--mesh": list(MESHES),
    "--granularity": list(GRANULARITIES),
    "--state-sharding": list(STATE_SHARDINGS),
    "--ef-state-dtype": ["bfloat16"],
    "--method": sorted(METHODS),
    "--compressor": sorted(COMPRESSORS),
    "--carrier": sorted(CARRIERS),
    "--downlink-carrier": sorted(DOWN_CARRIERS),
    "--moe-impl": list(MOE_IMPLS),
    "--optimizer": sorted(OPTIMIZERS),
}

_TYPES = {"int": int, "float": float, "str": str}


def add_flags(ap: argparse.ArgumentParser) -> None:
    """Register the RunSpec flag surface on a driver's parser. All defaults
    are None so ``RunSpec.from_args`` can distinguish 'unset' from an
    explicit value (needed for --spec overrides and resume handling)."""
    ap.add_argument("--spec", dest="spec_file", default=None, metavar="FILE",
                    help="JSON RunSpec file used as the base; explicit flags "
                         "override its fields")
    for flag, field, kind in _FLAGS:
        kw: Dict[str, Any] = {"dest": field, "default": None,
                              "help": _FLAG_HELP.get(flag)}
        if kind == "bool":
            kw["action"] = "store_true"
            # --no-<flag> lets a CLI override a truthy bool in a --spec
            # file back to False (None stays 'unset' → file/default wins)
            ap.add_argument(flag.replace("--", "--no-", 1), dest=field,
                            action="store_false", default=None,
                            help=f"negate {flag}")
        elif kind == "json":
            kw["type"] = json.loads
        elif kind == "schedule":
            kw["type"] = parse_schedule_flag
        elif kind == "participation":
            kw["type"] = parse_participation_flag
        elif kind == "hops":
            kw["type"] = parse_hops_flag
        else:
            kw["type"] = _TYPES[kind]
            if flag in _FLAG_CHOICES:
                kw["choices"] = _FLAG_CHOICES[flag]
        ap.add_argument(flag, **kw)


_DEFAULT = RunSpec()

# ---------------------------------------------------------------------------
# golden fixtures (results/specs/*.json): the DEFINITIONS live here so the
# files are regenerated mechanically (`python -m repro.launch.spec
# --regen-goldens`) instead of hand-edited — tests/test_spec.py byte-compares
# the files against these and fails on any drift either way
# ---------------------------------------------------------------------------

GOLDEN_SPECS: Dict[str, Dict[str, Any]] = {
    "train_smoke_ef21_sgdm": {"smoke": True},
    "fused_quickstart": {"carrier": "fused", "eta": 0.2,
                         "compressor_kw": {"block": 1024, "k_per_block": 16}},
    "dryrun_sparse_pod": {"arch": "gemma2-9b", "carrier": "sparse",
                          "compressor": "topk", "ratio": 0.01, "mesh": "pod",
                          "shape": "train_4k"},
    "quant4_multipod_zero": {"arch": "grok-1-314b", "carrier": "quant4",
                             "mesh": "multi_pod", "shape": "train_4k",
                             "client_granularity": "pod",
                             "state_sharding": "zero",
                             "ef_state_dtype": "bfloat16"},
    "bidir_quant4_down": {"smoke": True, "carrier": "quant4", "clients": 4,
                          "global_batch": 8, "seq_len": 64,
                          "downlink_carrier": "quant4",
                          "downlink_ratio": 0.02},
    # v3: a mixed 3-group schedule — dense norms/biases, quant4 embeds,
    # sparse everything else, with a quant4 downlink on the catch-all
    "mixed_schedule": {"smoke": True, "clients": 4, "global_batch": 8,
                       "seq_len": 64,
                       "groups": [
                           {"pattern": "norm|bias", "carrier": "dense"},
                           {"pattern": "embed", "carrier": "quant4",
                            "ratio": 0.05},
                           {"pattern": "*", "carrier": "sparse",
                            "ratio": 0.02, "downlink_carrier": "quant4",
                            "downlink_ratio": 0.05},
                       ]},
    # the one-launch fused quantized wire with comm/compute overlap
    # (DESIGN.md §10): the mega-kernel uplink on a production mesh
    "fused_quant8_overlap": {"carrier": "fused_quant8", "mesh": "pod",
                             "shape": "train_4k", "eta": 0.2,
                             "overlap": True,
                             "compressor_kw": {"block": 1024,
                                               "k_per_block": 16}},
    # v4: partial participation — a quarter cohort per round, seeded
    # (DESIGN.md §11; `--participation sampled:0.25:7`)
    "sampled_quarter": {"smoke": True, "clients": 4, "global_batch": 8,
                        "seq_len": 64,
                        "participation": {"mode": "sampled",
                                          "fraction": 0.25, "seed": 7}},
    # v5: two-tier hierarchical aggregation — 2 pods of 4 clients, dense
    # intra hop, quant4 cross-pod hop with its own EF memory per pod
    # (DESIGN.md §13; `--hops pods=2,cross=quant4:0.05`)
    "hierarchy_quant4_cross": {"smoke": True, "clients": 8, "global_batch": 8,
                               "seq_len": 64,
                               "hops": {"pods": 2,
                                        "cross_carrier": "quant4",
                                        "cross_ratio": 0.05}},
}


def regen_goldens(out_dir: str) -> List[str]:
    """Rewrite every golden fixture from GOLDEN_SPECS at the current schema.
    Returns the written paths."""
    import os
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for name in sorted(GOLDEN_SPECS):
        path = os.path.join(out_dir, f"{name}.json")
        with open(path, "w") as f:
            f.write(RunSpec(**GOLDEN_SPECS[name]).to_json(indent=1) + "\n")
        paths.append(path)
    return paths


def explicit_fields(args: argparse.Namespace,
                    ignore: Tuple[str, ...] = ()) -> List[str]:
    """RunSpec field names the user EXPLICITLY set on the command line (every
    flag defaults to None, so non-None means passed — an explicit flag equal
    to the field's default still counts). Drivers use this to decide whether
    a ``--resume`` should enforce the flag-built spec against the
    checkpoint's embedded one."""
    out = [field for _, field, _ in _FLAGS
           if field not in ignore and getattr(args, field, None) is not None]
    if getattr(args, "spec_file", None):
        out.append("spec_file")
    return out


def main(argv: Optional[List[str]] = None) -> None:
    """Emit RunSpec JSON without importing jax — the sweep-tooling entry:

      python -m repro.launch.spec --print --arch gemma2-9b --carrier sparse
      python -m repro.launch.spec --out sweep/cell_017.json --method ef21_sgd
    """
    ap = argparse.ArgumentParser(
        "repro.launch.spec",
        description="validate and print/write a RunSpec as canonical JSON")
    add_flags(ap)
    ap.add_argument("--print", dest="do_print", action="store_true",
                    help="print the canonical JSON to stdout")
    ap.add_argument("--out", default=None, help="write the JSON to a file")
    ap.add_argument("--regen-goldens", dest="regen_goldens",
                    action="store_true",
                    help="mechanically rewrite the golden fixtures under "
                         "--goldens-dir from spec.GOLDEN_SPECS at the "
                         "current schema, then exit")
    ap.add_argument("--goldens-dir", default="results/specs",
                    help="target directory for --regen-goldens")
    args = ap.parse_args(argv)
    if args.regen_goldens:
        for path in regen_goldens(args.goldens_dir):
            print(path)
        return
    spec = RunSpec.from_args(args)
    text = spec.to_json(indent=1)
    if args.out:
        import os
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.do_print or not args.out:
        print(text)


if __name__ == "__main__":
    main()
