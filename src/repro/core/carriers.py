"""Wire carriers: the transport format of one EF synchronization round.

EF21's separation result (Richtárik et al., 2021) is that the *wire format* of
the compressed innovation is independent of the *method semantics* — Algorithm 1
only requires that every client ships C(vᵢ − gᵢ) and receives meanᵢ(cᵢ). This
module makes that separation first-class (DESIGN.md §6): a :class:`Carrier`
owns how c travels, both runtimes (the vmap ``ef_round``, which the simulator
in core/simulate.py drives, and the shard_map ``ef_round_sharded``, in
core/distributed.py) dispatch through it, and methods never see the wire.

The carriers:

  DenseCarrier        paper-faithful: c is shipped as a dense d-word tensor and
                      the mean lowers to an all-reduce (lax.pmean on the mesh,
                      ``.mean(0)`` over the client axis in vmap runtimes).
  SparseBlockCarrier  fixed-(values, block-local int32 indices) wire for the
                      TopK family: an all-gather of 2·nb·kb words per client
                      followed by a local scatter-add. Block-local indices mean
                      no flat index ever exceeds the block size, so leaves with
                      > 2³¹ elements (grok expert weights) are safe. Plain TopK
                      is the single-block special case (block = d, exact global
                      TopK).
  FusedPallasCarrier  dense wire + the whole EF21-SGD(M) client chain
                      (pre_compress → Block-TopK → post_compress) fused into ONE
                      HBM pass via kernels/ef_update.py (~3× on the memory-
                      roofline term of the client update). Interpreted on the
                      CPU backend; the unfused dense plan runs for
                      methods/compressors the kernel does not cover.
  QuantCarrier        block-quantized wire (kernels/quantize.py +
                      kernels/ref.py oracles): per-block absmax scale + int8
                      (``quant8``) or packed-uint4 (``quant4``) mantissas, for
                      both dense payloads (quantized C(δ)) and sparse-block
                      payloads (quantized TopK values + block-local indices).
                      EF21's contraction argument absorbs the extra bounded
                      wire distortion into the residual (``local_c`` is the
                      decode of the wire, so quantization error is re-sent in
                      later rounds), cutting wire words another 4–8× on top of
                      sparsification. Aggregation always dequantizes BEFORE
                      the collective arithmetic: summing int8 mantissas across
                      blocks with different scales is not associative.
  FusedQuantCarrier   ``fused_quant8`` / ``fused_quant4``: the quantized wire
                      AND the one-launch uplink — EF21-SGD(M) update,
                      Block-TopK selection, absmax quantization, and the
                      EF-invariant g' = g + decode(wire) integration all in a
                      single mega-kernel (kernels/fused_round.py). The
                      payload is the block-dense quantized innovation at the
                      selection geometry (decodes bit-identically to the
                      sparse payload; see the class docstring for the
                      wire-words tradeoff).

Execution plans — a runtime asks ``carrier.plan(method, eta)`` and gets:

  'dense'  run the method's own update (pre → tree_compress → post or
           ``method.update``) and aggregate the dense message;
  'wire'   run pre_compress, then per-leaf encode → local_c → aggregate,
           then post_compress (message must equal the wire, method.wire_is_msg);
  'fused'  call ``carrier.fused_update`` which replaces the entire three-phase
           chain with the fused kernel; aggregate the dense c it returns;
  'fused_wire'
           call ``carrier.fused_wire_round`` — one mega-kernel launch per
           leaf produces (v', g', quantized wire) with the EF invariant
           integrated in-kernel, and the aggregated mean comes back with it
           (the aggregation needs the wire, so it cannot be split off).

``plan_with_reason`` additionally returns WHY a carrier degraded from its
native plan (empty reason = the native plan runs). Launch
surfaces print it, so a misconfigured run no longer looks identical to a
working one in logs.

Carriers are direction-aware (DESIGN.md §8): the same wire formats also ship
the DOWNLINK leg — the server's broadcast of its compressed innovation
C(g_server − h) against an EF21 server memory h. ``plan_down_with_reason``
is the downlink twin of ``plan_with_reason`` (no method enters: the broadcast
payload is always the compressed innovation, so only the compressor gates the
wire), ``downlink_round`` runs the encode → decode leg shared by every
runtime (aggregation is a no-op — one server, one message), and
``downlink_words`` is the honest broadcast word count.

Aggregation runs in one of two contexts, selected by keyword:

  aggregate(..., dp=n)       wire leaves carry a leading client axis (vmap
                             runtimes) — reduce over axis 0;
  aggregate(..., axes=(...)) wire leaves are client-local inside shard_map —
                             reduce with explicit named-axis collectives.

``wire_words`` is the honest per-client, per-message word count for benchmark
x-axes (values AND indices both count; a dense all-reduce counts d), exposed to
plots via ``Method.coords_per_message(d, carrier=...)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import compressors as comp_lib
from repro.telemetry import EF_AGGREGATE, EF_CLIENT, EF_COLLECTIVE

PyTree = Any
Wire = Any

# rng fold constant of the downlink leg — ONE value shared by every runtime
# (the broadcast must be one identical message on server and all clients, so
# its key is derived from the round rng BEFORE any per-client folding)
DOWNLINK_FOLD = 1 << 20


def pmean(x: jax.Array, axes) -> jax.Array:
    """``lax.pmean`` of one leaf over the client axes, as it computes it:
    the all-reduce (a ``psum``, under ``EF_COLLECTIVE`` with its operand
    recorded by ``telemetry.record_collective``), then the division by the
    number of clients outside the scope."""
    telemetry.record_collective("all-reduce", axes, x.size, x.dtype)
    with jax.named_scope(EF_COLLECTIVE):
        total = jax.lax.psum(x, axes)
    n = 1
    for a in (axes,) if isinstance(axes, str) else axes:
        n *= jax.lax.axis_size(a)
    return total / n


def all_gather(x: jax.Array, axis_name) -> jax.Array:
    """``lax.all_gather`` of one leaf over a client axis, under
    ``EF_COLLECTIVE`` with its operand recorded."""
    telemetry.record_collective("all-gather", axis_name, x.size, x.dtype)
    with jax.named_scope(EF_COLLECTIVE):
        return jax.lax.all_gather(x, axis_name)


def _ppermute(x: jax.Array, axis_name, perm) -> jax.Array:
    telemetry.record_collective("ppermute", axis_name, x.size, x.dtype)
    with jax.named_scope(EF_COLLECTIVE):
        return jax.lax.ppermute(x, axis_name, perm)


def ring_all_gather(x: PyTree, axis_name, fn=None) -> PyTree:
    """``lax.all_gather`` rebuilt as a ring of n−1 ``ppermute`` steps.

    Bit-identical transport: the result is stacked in axis-index order, exactly
    like ``lax.all_gather``'s leading axis — only the route differs. The point
    of the ring is *comm/compute overlap*: ``fn`` (when given) maps each chunk
    as it lands, and because chunk s never depends on permute s+1, XLA is free
    to run ``fn`` on the chunk in hand while the next permute is in flight —
    double-buffered decode behind the collective. ``fn`` must be elementwise
    per chunk (applied chunk-by-chunk here vs. once on the gathered stack must
    be the same bits); identity when omitted.

    Degenerates to a no-op stack on a 1-device axis."""
    if fn is None:
        fn = lambda c: c                                 # noqa: E731
    n = int(jax.lax.axis_size(axis_name))
    if n == 1:
        return jax.tree_util.tree_map(lambda a: a[None], fn(x))
    perm = [(i, (i + 1) % n) for i in range(n)]
    buf, chunks = x, [fn(x)]
    for _ in range(n - 1):
        # chunk s arrives from ring-neighbor s hops back while fn(chunk s−1)
        # is still runnable — the double buffer is (buf, chunks[-1])
        buf = jax.tree_util.tree_map(
            lambda a: _ppermute(a, axis_name, perm), buf)
        chunks.append(fn(buf))
    stacked = jax.tree_util.tree_map(lambda *cs: jnp.stack(cs), *chunks)
    # chunks[s] came from axis index (me − s) mod n; re-index to axis order
    me = jax.lax.axis_index(axis_name)
    order = jnp.mod(me - jnp.arange(n), n)
    return jax.tree_util.tree_map(lambda s: jnp.take(s, order, axis=0),
                                  stacked)


def sparse_geom(comp, d: int) -> Tuple[int, int, int]:
    """(nb, block, kb) geometry of the fixed-size TopK-family wire for a flat
    (d,) leaf. Plain TopK = one block spanning the leaf (exact global TopK);
    BlockTopK geometry is d-aware (``BlockTopK.geom``: sub-block leaves get a
    proportional budget, not the degenerate full-block K); shared by the
    sparse and quantized carriers."""
    if isinstance(comp, comp_lib.BlockTopK):
        return comp.geom(d)
    if isinstance(comp, comp_lib.TopK):
        return 1, d, comp._k(d)
    raise ValueError(
        f"no fixed-size sparse wire for {type(comp).__name__}")


def sparse_select(comp, delta: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The TopK-family wire selection shared by every carrier that ships it:
    pad to whole blocks, per-block top-|·|-k. Returns (vals, idx), both
    (nb, kb), idx block-LOCAL and sorted by magnitude rank. One
    implementation so tie-breaking/padding can never diverge between the
    sparse and quantized wires."""
    nb, block, kb = sparse_geom(comp, delta.size)
    xb = jnp.pad(delta, (0, nb * block - delta.size)).reshape(nb, block)
    _, idx = jax.lax.top_k(jnp.abs(xb), kb)
    vals = jnp.take_along_axis(xb, idx, axis=1)
    return vals, idx


def scatter_blocks(vals: jax.Array, idx: jax.Array, *, nb: int, block: int,
                   d: int, dtype) -> jax.Array:
    """Scatter one client's (nb, kb) block-wire values back to a flat (d,)
    tensor — the shared decode of the block-sparse wires. ``set`` semantics:
    indices are unique within one wire; cross-client aggregation must
    scatter-ADD instead (see the aggregate methods)."""
    rows = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32)[:, None],
                            idx.shape)
    buf = jnp.zeros((nb, block), dtype)
    buf = buf.at[rows, idx.astype(jnp.int32)].set(vals)
    return buf.reshape(-1)[:d]


def client_mean(vals: jax.Array, dp: int, d: int) -> jax.Array:
    """Mean over clients of decoded block rows: ``vals`` is (dp·nb, block),
    each client's nb rows in turn; returns the flat (d,) mean. The rows keep
    their (nb, block) shape through the mean: a (dp, nb·block) view of a
    full-width leaf makes the TPU compiler emit hundreds of MB of
    relayout code and take minutes to compile it."""
    return vals.reshape(dp, -1, vals.shape[-1]).mean(0).reshape(-1)[:d]


@dataclasses.dataclass(frozen=True)
class Carrier:
    """Base carrier. Frozen dataclass → hashable, usable inside jit statics."""

    name: str = "abstract"
    # comm/compute overlap (EFConfig.overlap / RunSpec --overlap): gather-wire
    # carriers transport their all-gathers as a ppermute ring and decode each
    # chunk while the next is in flight (ring_all_gather). Bit-identical to
    # the blocking path by construction; a no-op for all-reduce wires (dense
    # psum has no per-client chunks to pipeline).
    overlap: bool = False

    def _gather(self, x: PyTree, axis_name, fn=None) -> PyTree:
        """The collective behind every gather-wire aggregate: blocking
        ``lax.all_gather`` by default, the overlapped ppermute ring when
        ``overlap`` is set. ``fn`` maps chunks as they arrive (overlap path)
        or the whole stack at once (blocking path) — same bits either way."""
        if self.overlap:
            return ring_all_gather(x, axis_name, fn)
        gathered = jax.tree_util.tree_map(
            lambda a: all_gather(a, axis_name), x)
        if fn is None:
            return gathered
        return jax.vmap(fn)(gathered)

    # -- plan selection ------------------------------------------------------
    def plan_with_reason(self, method, eta=None) -> Tuple[str, str]:
        """(plan, reason): plan is 'dense' | 'wire' | 'fused' | 'fused_wire'.
        The reason is
        the empty string when the carrier's native plan runs, and a
        human-readable explanation whenever it degraded to 'dense' — runtimes
        stay silent, but launch surfaces print it so a degraded configuration
        is visible in logs."""
        return "dense", "abstract base carrier has no wire format"

    def plan(self, method, eta=None) -> str:
        """'dense' | 'wire' | 'fused' — how a runtime should execute one round
        of ``method``. Carriers must degrade to 'dense' (always correct) when
        they cannot ship this method's messages."""
        return self.plan_with_reason(method, eta)[0]

    # -- downlink (server → client broadcast) --------------------------------
    def plan_down_with_reason(self, comp: comp_lib.Compressor
                              ) -> Tuple[str, str]:
        """(plan, reason) for the DOWNLINK leg: the server broadcasts ONE
        message C(g_server − h) and every client decodes it — there is no
        aggregation, so the plan depends only on the compressor (no method:
        the broadcast payload is always the compressed innovation itself).
        'wire' ships the carrier's native format; 'dense' ships the dense
        C(δ) tensor (always correct). A non-empty reason explains a
        degradation, exactly like ``plan_with_reason``."""
        return "dense", "abstract base carrier has no wire format"

    def plan_down(self, comp: comp_lib.Compressor) -> str:
        return self.plan_down_with_reason(comp)[0]

    # -- per-client wire API (flat (d,) leaves) ------------------------------
    def encode(self, comp: comp_lib.Compressor, delta: jax.Array,
               rng: Optional[jax.Array] = None) -> Wire:
        """delta: flat (d,). Returns the wire representation of C(delta)."""
        raise NotImplementedError

    def encode_local(self, comp: comp_lib.Compressor, delta: jax.Array,
                     rng: Optional[jax.Array] = None) -> Wire:
        """``encode`` for the client-local (shard_map, unbatched) context —
        carriers with a Pallas fast path override this (the batched runtimes
        keep the pure-jnp ``encode`` so no vmap-of-pallas_call is emitted).
        Must be bit-compatible with ``encode``."""
        return self.encode(comp, delta, rng)

    def decode(self, comp: comp_lib.Compressor, wire: Wire, *, d: int,
               dtype) -> jax.Array:
        """The dense decode of one client's wire. ``local_c`` is DEFINED as
        this decode (not an independent recomputation of C(δ)): client state
        and the server aggregate must agree on exactly what was shipped, or
        error feedback would never re-send mass lost to ties/quantization."""
        raise NotImplementedError

    def local_c(self, comp: comp_lib.Compressor, delta: jax.Array,
                wire: Wire) -> jax.Array:
        """The dense C(delta) the client keeps locally for its gᵢ update —
        never transmitted. Returns flat (d,)."""
        return self.decode(comp, wire, d=delta.size, dtype=delta.dtype)

    def decode_add(self, comp: comp_lib.Compressor, wire: Wire,
                   base: jax.Array, *, d: int, dtype) -> jax.Array:
        """``base + decode(wire)`` as one logical launch — the downlink's
        h-integration hook (``downlink_round_integrate``). The default IS
        that expression, so overriding carriers (quantized wires run the
        fused dequantize+add Pallas kernel on TPU) stay bit-compatible
        within float-compilation tolerance. ``base``: flat (d,)."""
        return base + self.decode(comp, wire, d=d, dtype=dtype)

    def aggregate(self, comp: comp_lib.Compressor, wire: Wire, *, d: int,
                  dtype, dp: Optional[int] = None,
                  axes: Optional[Tuple[str, ...]] = None) -> jax.Array:
        """meanᵢ(cᵢ) from the wire. Exactly one of ``dp`` (leading-axis vmap
        layout) / ``axes`` (named shard_map axes) must be given. Returns flat
        (d,)."""
        raise NotImplementedError

    # -- accounting ----------------------------------------------------------
    def wire_words(self, comp: comp_lib.Compressor, d: int) -> float:
        """Words one client puts on the wire per message of dimension d."""
        raise NotImplementedError

    # -- fusion hook ---------------------------------------------------------
    def fused_update(self, method, grads: PyTree, state: dict, *,
                     eta=None, batched: bool = False):
        raise NotImplementedError(f"carrier {self.name!r} does not fuse")


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseCarrier(Carrier):
    """Paper-faithful wire: the dense tensor C(delta) itself; the mean is a
    d-word all-reduce over the client axes (what the paper's own simulations
    do — no wire savings; the §Perf baseline)."""

    name: str = "dense"

    def plan_with_reason(self, method, eta=None):
        return "dense", ""          # dense IS this carrier's native wire

    def plan_down_with_reason(self, comp):
        return "dense", ""          # ...in both directions

    def encode(self, comp, delta, rng=None):
        return comp(delta, rng)

    def decode(self, comp, wire, *, d, dtype):
        return wire

    def aggregate(self, comp, wire, *, d, dtype, dp=None, axes=None):
        if axes is not None:
            return pmean(wire, axes)
        return wire.mean(0)

    def wire_words(self, comp, d):
        return float(d)


# ---------------------------------------------------------------------------
# sparse block
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparseBlockCarrier(Carrier):
    """Fixed-size (values, block-local indices) wire for the TopK family.

    Collective bytes drop from d to 2·nb·kb per client: the mean is an
    all-gather of the small arrays followed by a local scatter-ADD (index
    collisions across clients must SUM — ``.at[].add``). ``local_c`` is the
    exact decode of the wire, so client state and server aggregate always
    agree on what was transmitted (see local_c)."""

    name: str = "sparse"

    def plan_with_reason(self, method, eta=None):
        if not method.wire_is_msg:
            return "dense", (
                f"method {method.name!r} transmits a transform of c "
                "(wire_is_msg=False); a non-dense wire cannot ship it")
        if not self.supports(method.compressor):
            return "dense", (
                f"compressor {type(method.compressor).__name__} has no "
                "deterministic fixed-size (values, indices) wire")
        return "wire", ""

    def plan_down_with_reason(self, comp):
        # no wire_is_msg question on the downlink: the broadcast IS the
        # compressed innovation, so only the compressor gates the wire
        if not self.supports(comp):
            return "dense", (
                f"compressor {type(comp).__name__} has no deterministic "
                "fixed-size (values, indices) wire")
        return "wire", ""

    def supports(self, comp) -> bool:
        # has_sparse_carrier is the compressor's opt-in; the isinstance check
        # narrows to the families whose fixed-size geometry _geom understands
        # (RandK opts in but needs rng-dependent indices — not expressible as
        # a deterministic block wire, so it degrades to dense)
        return (comp.has_sparse_carrier
                and isinstance(comp, (comp_lib.TopK, comp_lib.BlockTopK)))

    def _geom(self, comp, d: int) -> Tuple[int, int, int]:
        return sparse_geom(comp, d)

    def encode(self, comp, delta, rng=None):
        vals, idx = sparse_select(comp, delta)           # (nb, kb), sorted
        return vals, idx.astype(jnp.int32)               # block-LOCAL indices

    def decode(self, comp, wire, *, d, dtype):
        # exact decode of the wire (scatter of the shipped values), NOT a
        # threshold mask: the client's gᵢ update must see precisely what the
        # server aggregated, or a tie at the kb-th rank would leave mass the
        # client believes transmitted but the server never received — error
        # feedback would then never re-send it
        vals, idx = wire
        nb, block, _ = self._geom(comp, d)
        return scatter_blocks(vals, idx, nb=nb, block=block, d=d,
                              dtype=dtype)

    def aggregate(self, comp, wire, *, d, dtype, dp=None, axes=None):
        vals, idx = wire
        nb, block, kb = self._geom(comp, d)
        if axes is not None:
            n = 1
            for a in axes:                               # explicit wire
                n = n * jax.lax.axis_size(a)
                vals, idx = self._gather((vals, idx), a)
            vals = vals.reshape(-1, nb, kb)
            idx = idx.reshape(-1, nb, kb)
        else:
            n = dp                                       # (dp, nb, kb) layout
        rows = jnp.broadcast_to(
            jnp.arange(nb, dtype=jnp.int32)[None, :, None], idx.shape)
        buf = jnp.zeros((nb, block), dtype).at[rows, idx].add(vals) / n
        return buf.reshape(-1)[:d]

    def wire_words(self, comp, d):
        nb, _, kb = self._geom(comp, d)
        return 2.0 * nb * kb                             # values + int32 idx


# ---------------------------------------------------------------------------
# fused Pallas
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedPallasCarrier(DenseCarrier):
    """Dense wire + the whole EF21-SGD(M) client update in one HBM pass.

    ``fused_update`` replaces pre_compress → C(·) → post_compress for
    EF21SGDM / EF21SGD with a BlockTopK compressor by a single call into
    ``kernels/ef_update.py::ef21_sgdm_update`` per leaf (EF21SGD is the η = 1
    special case: v' = grad). The kernel needs a *static* momentum, so the
    plan degrades to 'dense' whenever η is traced (time-varying schedules).

    The kernel runs compiled on a TPU and in the Pallas interpreter on the
    CPU backend (``kernels/backend.py::interpret``).
    """

    name: str = "fused"

    _LANES = 128                     # TPU vector lane width (f32)

    @classmethod
    def _kernel_geom(cls, comp, d: int) -> Tuple[int, int, int]:
        """(nb, launch_block, kb): the d-aware selection geometry
        (``BlockTopK.geom``) with a SINGLE-block leaf's launch block rounded
        up to whole TPU lanes, so the sub-block leaves this geometry
        introduced never hand Mosaic an unaligned tile — padding zeros in
        the row cannot change the top-kb selection. Multi-block leaves keep
        the geometry block untouched (rounding it would shift the row
        boundaries off the block boundaries); their alignment is whatever
        the compressor's own block is, as it always was."""
        nb, block, kb = comp.geom(d)
        if nb == 1:
            block = -(-block // cls._LANES) * cls._LANES
        return nb, block, kb

    def plan_with_reason(self, method, eta=None):
        if method.name not in ("ef21_sgdm", "ef21_sgd"):
            return "dense", (
                f"the fused kernel implements the EF21-SGD(M) client chain "
                f"only, not {method.name!r}")
        if not isinstance(method.compressor, comp_lib.BlockTopK):
            return "dense", (
                f"the fused kernel compresses with BlockTopK only, not "
                f"{type(method.compressor).__name__}")
        if not (eta is None or isinstance(eta, (int, float))):
            return "dense", ("momentum η is traced (time-varying schedule); "
                             "the kernel needs a static η to bake in")
        return "fused", ""

    def plan_down_with_reason(self, comp):
        return "dense", (
            "the fused kernel fuses the UPLINK client update; the downlink "
            "broadcast has no fused path — use dense, sparse or quant")

    def fused_update(self, method, grads, state, *, eta=None,
                     batched: bool = False):
        """One fused HBM pass per leaf. ``grads``/``state`` leaves are either
        client-local (shard_map runtime, ``batched=False``) or carry a leading
        client axis (vmap runtimes, ``batched=True`` — clients become extra
        tile rows, so no vmap-of-pallas_call is ever emitted).
        Returns (c_tree, new_state)."""
        from repro.kernels import ef_update as ef_kernel
        from repro.kernels.backend import interpret

        comp = method.compressor
        if method.name == "ef21_sgd":
            eta_f = 1.0                                  # v' = grad exactly
            v_tree = state["g"]
        else:
            eta_f = float(eta) if eta is not None else float(method.eta)
            v_tree = state["v"]
        interp = interpret()

        g_leaves, treedef = jax.tree_util.tree_flatten(state["g"])
        v_leaves = jax.tree_util.tree_leaves(v_tree)
        grad_leaves = jax.tree_util.tree_leaves(grads)

        v_out, g_out, c_out = [], [], []
        for grad, v, g in zip(grad_leaves, v_leaves, g_leaves):
            # d-aware geometry per leaf (BlockTopK.geom): the kernel selects
            # the same kb the dense reference selection uses, so sub-block
            # leaves stay consistent across carriers. The LAUNCH block is
            # rounded up to whole TPU lanes (zeros pad the row — exactly the
            # trailing-partial-block case the kernel always handled: padding
            # never outranks a real value, and a 0 threshold keeps
            # everything, so the selection over the padded row equals the
            # selection over the geometry block).
            if batched:
                # pad each client's leaf to whole blocks FIRST so client
                # boundaries and block boundaries coincide in the flat view
                dp = grad.shape[0]
                d = grad[0].size
                nb, block, kb = self._kernel_geom(comp, d)
                pad = nb * block - d

                def prep(x):
                    return jnp.pad(x.reshape(dp, d), ((0, 0), (0, pad)))

                v2, g2, c = ef_kernel.ef21_sgdm_update(
                    prep(grad), prep(v), prep(g), eta=eta_f, block=block,
                    k=kb, interpret=interp)
                unprep = lambda x: x[:, :d].reshape(grad.shape)  # noqa: E731
                v2, g2, c = unprep(v2), unprep(g2), unprep(c)
            else:
                _, block, kb = self._kernel_geom(comp, grad.size)
                v2, g2, c = ef_kernel.ef21_sgdm_update(
                    grad, v, g, eta=eta_f, block=block, k=kb,
                    interpret=interp)
            v_out.append(v2)
            g_out.append(g2)
            c_out.append(c)

        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)  # noqa: E731
        c_tree = unf(c_out)
        g_new = method._cast(unf(g_out))
        if method.name == "ef21_sgd":
            new_state = {"g": g_new}
        else:
            new_state = {"v": method._cast(unf(v_out)), "g": g_new}
        return c_tree, new_state


# ---------------------------------------------------------------------------
# quantized wires
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantCarrier(Carrier):
    """Block-quantized wire: per-block absmax scale (1 f32 word) + ``bits``-bit
    mantissas (int8, or two uint4 packed per byte), in one of two payloads:

      sparse-block  for the TopK family (the sparse carrier's geometry): the
                    kb selected values of each block are quantized against one
                    shared scale and travel with their block-local indices
                    (int16 when the block fits, else int32) —
                    nb·(1 + kb·(bits/32 + idx_words)) words/client.
      dense         for every other deterministic compressor: C(δ) itself is
                    quantized in ``qblock``-sized blocks —
                    nbq·(1 + qblock·bits/32) words/client.

    ``local_c`` is the decode of the wire (base-class invariant), so EF21's
    residual absorbs the quantization error and re-sends it in later rounds —
    the same contraction argument that lets Fatkhullin et al. treat C as a
    black box covers the extra bounded wire distortion (``composed_err_factor``
    gives the predicted Definition-1 constant of decode∘quantize∘C).

    Aggregation ALWAYS dequantizes before the collective arithmetic: int8
    mantissas under different per-block scales do not form an associative
    monoid, so an int8 all-reduce would be wrong. On the shard_map runtime the
    sparse payload all-gathers the still-quantized wire (the savings are on
    the links) and dequantizes locally; the dense payload dequantizes locally
    and psums f32 (its collective operand is dense — the wire savings of the
    dense payload are client→server bytes, not all-reduce bytes).

    The unbatched (shard_map) encode runs the Pallas codec
    (kernels/quantize.py, interpreted on CPU); the vmap runtimes run the
    bit-compatible pure-jnp oracle (kernels/ref.py) so no vmap-of-pallas_call
    is ever emitted.
    """

    name: str = "quant8"
    bits: int = 8
    qblock: int = 256          # dense-payload quantization block (even)

    # -- plan ---------------------------------------------------------------
    def plan_with_reason(self, method, eta=None):
        if not method.wire_is_msg:
            return "dense", (
                f"method {method.name!r} transmits a transform of c "
                "(wire_is_msg=False); a non-dense wire cannot ship it")
        if method.compressor.needs_rng:
            return "dense", (
                f"compressor {type(method.compressor).__name__} draws "
                "randomness inside encode; the quantized wire ships "
                "deterministic compressors only")
        return "wire", ""

    def plan_down_with_reason(self, comp):
        if comp.needs_rng:
            return "dense", (
                f"compressor {type(comp).__name__} draws randomness inside "
                "encode; the quantized wire ships deterministic compressors "
                "only")
        return "wire", ""

    def _sparse_ok(self, comp) -> bool:
        return (comp.has_sparse_carrier
                and isinstance(comp, (comp_lib.TopK, comp_lib.BlockTopK)))

    @staticmethod
    def _idx_dtype(block: int):
        # block-LOCAL indices: int16 halves the index words whenever the
        # block fits (the common case); the single-block TopK wire on a large
        # leaf falls back to int32
        return jnp.int16 if block <= 2 ** 15 - 1 else jnp.int32

    # -- wire ---------------------------------------------------------------
    def encode(self, comp, delta, rng=None):
        from repro.kernels import ref as kref
        if self._sparse_ok(comp):
            _, block, _ = sparse_geom(comp, delta.size)
            vals, idx = sparse_select(comp, delta)
            q, scales = kref.block_quantize_ref(vals, self.bits)
            return q, scales, idx.astype(self._idx_dtype(block))
        c = comp(delta, rng).astype(jnp.float32)
        nbq = -(-delta.size // self.qblock)
        cb = jnp.pad(c, (0, nbq * self.qblock - c.size)).reshape(nbq, self.qblock)
        q, scales = kref.block_quantize_ref(cb, self.bits)
        return q, scales

    def encode_local(self, comp, delta, rng=None):
        # client-local (shard_map) context: the Pallas codec quantizes the
        # dense payload in one kernel pass (interpreted on CPU); the sparse
        # payload quantizes (nb, kb) value rows — lane-unfriendly tiles, so it
        # stays on the jnp oracle everywhere
        if self._sparse_ok(comp):
            return self.encode(comp, delta, rng)
        from repro.kernels import quantize as qz
        from repro.kernels.backend import interpret
        c = comp(delta, rng).astype(jnp.float32)
        return qz.block_quantize(c, block=self.qblock, bits=self.bits,
                                 interpret=interpret())

    def decode(self, comp, wire, *, d, dtype):
        # payload dispatch on the same predicate encode used — never on the
        # wire's shape, so a future layout change fails loudly instead of
        # being sniffed into the wrong branch
        from repro.kernels import ref as kref
        if self._sparse_ok(comp):                        # sparse payload
            q, scales, idx = wire
            nb, block, kb = sparse_geom(comp, d)
            vals = kref.block_dequantize_ref(q, scales, bits=self.bits,
                                             cols=kb)
            return scatter_blocks(vals, idx, nb=nb, block=block, d=d,
                                  dtype=jnp.float32).astype(dtype)
        q, scales = wire                                 # dense payload
        vals = kref.block_dequantize_ref(q, scales, bits=self.bits,
                                         cols=self.qblock)
        return vals.reshape(-1)[:d].astype(dtype)

    def decode_add(self, comp, wire, base, *, d, dtype):
        # dense payload on TPU: dequantize + integrate in ONE Pallas launch
        # (kernels/fused_round.py::dequant_add). On CPU the default jnp
        # expression already compiles to one fused XLA computation, and the
        # sparse payload's scatter decode has no tiled kernel — both take
        # the base-class path. Same math either way, so the h-integration
        # stays within float-compilation tolerance across backends.
        from repro.kernels.backend import interpret
        if self._sparse_ok(comp) or interpret():
            return super().decode_add(comp, wire, base, d=d, dtype=dtype)
        from repro.kernels import fused_round as fr
        q, scales = wire
        out = fr.dequant_add(q, scales, base.astype(jnp.float32), d=d,
                             block=self.qblock, bits=self.bits,
                             interpret=False)
        return out.astype(dtype)

    def aggregate(self, comp, wire, *, d, dtype, dp=None, axes=None):
        from repro.kernels import ref as kref
        if self._sparse_ok(comp):                        # sparse payload
            q, scales, idx = wire
            nb, block, kb = sparse_geom(comp, d)
            if axes is not None and len(axes) == 1:
                # gather the QUANTIZED wire (the savings live on the links)
                # and decode each client's chunk as it arrives — under
                # ``overlap`` the ring keeps the next permute in flight while
                # this chunk dequantizes; the blocking path applies the same
                # per-chunk decode to the gathered stack (same bits)
                n = jax.lax.axis_size(axes[0])
                vals, idx = self._gather(
                    (q, scales, idx), axes[0],
                    fn=lambda w: (kref.block_dequantize_ref(
                        w[0], w[1], bits=self.bits, cols=kb), w[2]))
                vals = vals.reshape(-1, nb, kb)
                idx = idx.reshape(-1, nb, kb)
            else:
                if axes is not None:
                    n = 1
                    for a in axes:
                        n = n * jax.lax.axis_size(a)
                        q, scales, idx = self._gather((q, scales, idx), a)
                    q = q.reshape(-1, nb, q.shape[-1])
                    scales = scales.reshape(-1, nb)
                    idx = idx.reshape(-1, nb, kb)
                else:
                    n = dp                               # (dp, nb, ·) layout
                vals = kref.block_dequantize_ref(
                    q.reshape(-1, q.shape[-1]), scales.reshape(-1),
                    bits=self.bits, cols=kb).reshape(-1, nb, kb)
            rows = jnp.broadcast_to(
                jnp.arange(nb, dtype=jnp.int32)[None, :, None], idx.shape)
            buf = jnp.zeros((nb, block), jnp.float32)
            buf = buf.at[rows, idx.astype(jnp.int32)].add(vals) / n
            return buf.reshape(-1)[:d].astype(dtype)
        if axes is not None:                             # dense payload:
            deq = self.decode(comp, wire, d=d, dtype=jnp.float32)
            return pmean(deq, axes).astype(dtype)  # dequant THEN psum
        q, scales = wire                                 # (dp, nbq, ·) layout
        dp_, nbq = scales.shape
        vals = kref.block_dequantize_ref(
            q.reshape(dp_ * nbq, q.shape[-1]), scales.reshape(-1),
            bits=self.bits, cols=self.qblock)
        return client_mean(vals, dp_, d).astype(dtype)

    # -- accounting ---------------------------------------------------------
    def wire_words(self, comp, d):
        frac = self.bits / 32.0                          # 4-bit = 1/8 word
        if self._sparse_ok(comp):
            nb, block, kb = sparse_geom(comp, d)
            idx_words = 0.5 if block <= 2 ** 15 - 1 else 1.0
            return nb * (1.0 + kb * (frac + idx_words))
        nbq = -(-d // self.qblock)
        return nbq * (1.0 + self.qblock * frac)

    def quant_eps(self, comp, d: int) -> float:
        """Relative per-message quantization error bound: with B elements per
        scale, ‖Q(x) − x‖² ≤ Σ_b B·(absmax_b/2qmax)² ≤ B/(4·qmax²)·‖x‖²."""
        qmax = 2 ** (self.bits - 1) - 1
        if self._sparse_ok(comp):
            _, _, kb = sparse_geom(comp, d)
            per_scale = kb
        else:
            per_scale = min(self.qblock, d)
        return per_scale / (4.0 * qmax * qmax)

    def composed_err_factor(self, comp, d: int) -> float:
        """Definition-1 constant of the composed compressor decode∘Q∘C:
        ‖QC(x) − x‖ ≤ ‖QC(x) − C(x)‖ + ‖C(x) − x‖ ≤ (√ε + √(1−α))·‖x‖
        (C is a norm-contraction, so ‖C(x)‖ ≤ ‖x‖). Returns (√(1−α) + √ε)²."""
        root = ((1.0 - comp.alpha(d)) ** 0.5
                + self.quant_eps(comp, d) ** 0.5)
        return root * root

    def composed_alpha(self, comp, d: int) -> float:
        """Predicted α of the composed compressor (0 when the bound is
        vacuous — the wire still works, EF just loses the rate guarantee)."""
        return max(0.0, 1.0 - self.composed_err_factor(comp, d))


# ---------------------------------------------------------------------------
# fused quantized wires (the one-launch mega-kernel carriers)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedQuantCarrier(QuantCarrier):
    """Quantized wire + the ENTIRE uplink client round in one kernel launch.

    Where ``fused`` fuses the EF21-SGD(M) update but still ships a dense f32
    wire, and ``quant8``/``quant4`` quantize the wire but launch the update,
    selection, and codec as separate kernels, this carrier runs
    ``kernels/fused_round.py::ef21_sgdm_topk_quant`` — momentum update,
    Block-TopK selection, absmax quantization, AND the EF-invariant
    g' = g + decode(wire) integration — as ONE HBM pass (plan
    ``'fused_wire'``), and the quantized payload is what travels.

    Payload: the BLOCK-DENSE quantized form of the k-sparse innovation at the
    selection geometry — q (nb, block·bits/8 bytes) + one f32 scale per
    selection block. The quantization row IS the selection block, so the
    masked row's absmax equals the selected values' absmax and masked-out
    zeros get mantissa 0 exactly: this payload decodes bit-identically to the
    (vals, idx) sparse payload, without the TPU-hostile in-kernel compaction
    a sparse payload would need. The honest cost is on the links:
    nb·(1 + block·bits/32) words/client — bits/32 of the dense/fused
    carriers' d words, but MORE than quant8/quant4's kb-sized sparse payload.
    Pick this carrier when the round is launch/HBM-bound (the mega-kernel is
    the win); pick plain quant8/quant4 when the links are the bottleneck.

    For methods/compressors the mega-kernel does not cover, the plan degrades
    to the ordinary unfused ``'wire'`` (same payload, oracle codec) — still
    correct, just three launches — or to ``'dense'`` under the base
    QuantCarrier's own degradations. Launch surfaces treat a degraded
    fused_quant like a degraded ``fused``: a hard misconfiguration error.

    Aggregation dequantizes locally and pmeans f32 (the dense-payload rule:
    mantissas under different scales are not associative), so ``overlap`` is
    a no-op here — there is no per-client gather to pipeline.
    """

    name: str = "fused_quant8"

    def _fused_geom(self, comp, d: int) -> Tuple[int, int, int]:
        # the mega-kernel's launch geometry: selection geometry with
        # single-block leaves lane-rounded (FusedPallasCarrier._kernel_geom).
        # A degraded plan still routes the wire codec here with a plain
        # (global) TopK compressor — one block spanning the leaf, same lane
        # rounding, no ``BlockTopK.geom`` to call
        if isinstance(comp, comp_lib.BlockTopK):
            return FusedPallasCarrier._kernel_geom(comp, d)
        nb, block, kb = sparse_geom(comp, d)
        if nb == 1:
            lanes = FusedPallasCarrier._LANES
            block = -(-block // lanes) * lanes
        return nb, block, kb

    # -- plan ---------------------------------------------------------------
    def plan_with_reason(self, method, eta=None):
        plan, reason = super().plan_with_reason(method, eta)
        if plan != "wire":
            return plan, reason                          # dense degradation
        if method.name not in ("ef21_sgdm", "ef21_sgd"):
            return "wire", (
                f"the fused wire kernel implements the EF21-SGD(M) client "
                f"chain only, not {method.name!r}; running the unfused "
                "quantized wire")
        if not isinstance(method.compressor, comp_lib.BlockTopK):
            return "wire", (
                f"the fused wire kernel compresses with BlockTopK only, not "
                f"{type(method.compressor).__name__}; running the unfused "
                "quantized wire")
        if not (eta is None or isinstance(eta, (int, float))):
            return "wire", (
                "momentum η is traced (time-varying schedule); the kernel "
                "needs a static η to bake in — running the unfused "
                "quantized wire")
        if self.bits == 4 and method.compressor.block % 2:
            # multi-block leaves launch at the compressor's own block width;
            # single-block leaves lane-round to 128 and can never be odd
            return "wire", (
                "uint4 packing needs an even BlockTopK block; running the "
                "unfused quantized wire")
        return "fused_wire", ""

    # -- wire (block-dense payload at the selection geometry) ----------------
    def encode(self, comp, delta, rng=None):
        from repro.kernels import ref as kref
        if not self._sparse_ok(comp):
            return super().encode(comp, delta, rng)
        nb, block, _ = self._fused_geom(comp, delta.size)
        c = comp(delta, rng).astype(jnp.float32)         # threshold-mask C(δ)
        cb = jnp.pad(c, (0, nb * block - c.size)).reshape(nb, block)
        return kref.block_quantize_ref(cb, self.bits)

    def encode_local(self, comp, delta, rng=None):
        if not self._sparse_ok(comp):
            return super().encode_local(comp, delta, rng)
        return self.encode(comp, delta, rng)

    def decode(self, comp, wire, *, d, dtype):
        from repro.kernels import ref as kref
        if not self._sparse_ok(comp):
            return super().decode(comp, wire, d=d, dtype=dtype)
        q, scales = wire
        _, block, _ = self._fused_geom(comp, d)
        vals = kref.block_dequantize_ref(q, scales, bits=self.bits,
                                         cols=block)
        return vals.reshape(-1)[:d].astype(dtype)

    def decode_add(self, comp, wire, base, *, d, dtype):
        # the block-dense payload at the fused launch geometry runs the same
        # one-launch dequantize+add kernel as the dense quant payload on TPU;
        # on CPU the default jnp expression (one fused XLA computation) —
        # bit-compatible within float-compilation tolerance either way
        from repro.kernels.backend import interpret
        if not self._sparse_ok(comp) or interpret():
            return super().decode_add(comp, wire, base, d=d, dtype=dtype)
        from repro.kernels import fused_round as fr
        q, scales = wire
        _, block, _ = self._fused_geom(comp, d)
        out = fr.dequant_add(q, scales, base.astype(jnp.float32), d=d,
                             block=block, bits=self.bits, interpret=False)
        return out.astype(dtype)

    def aggregate(self, comp, wire, *, d, dtype, dp=None, axes=None):
        from repro.kernels import ref as kref
        if not self._sparse_ok(comp):
            return super().aggregate(comp, wire, d=d, dtype=dtype, dp=dp,
                                     axes=axes)
        if axes is not None:                             # dense-payload rule:
            deq = self.decode(comp, wire, d=d, dtype=jnp.float32)
            return pmean(deq, axes).astype(dtype)  # dequant THEN psum
        q, scales = wire                                 # (dp, nb, ·) layout
        _, block, _ = self._fused_geom(comp, d)
        dp_, nb = scales.shape
        vals = kref.block_dequantize_ref(
            q.reshape(dp_ * nb, q.shape[-1]), scales.reshape(-1),
            bits=self.bits, cols=block)
        return client_mean(vals, dp_, d).astype(dtype)

    # -- accounting ---------------------------------------------------------
    def wire_words(self, comp, d):
        if not self._sparse_ok(comp):
            return super().wire_words(comp, d)
        nb, block, _ = self._fused_geom(comp, d)
        return nb * (1.0 + block * self.bits / 32.0)

    def quant_eps(self, comp, d: int) -> float:
        # one scale per SELECTION block (not per kb values): the absmax is
        # still the selected values' absmax, and only the ≤ block selected
        # slots carry error mass, but the bound must count the slots a scale
        # covers — use the selection block for an honest constant
        if not self._sparse_ok(comp):
            return super().quant_eps(comp, d)
        qmax = 2 ** (self.bits - 1) - 1
        _, _, kb = sparse_geom(comp, d)
        return kb / (4.0 * qmax * qmax)

    # -- the one-launch round ------------------------------------------------
    def fused_wire_round(self, method, grads: PyTree, state: dict, *,
                         eta=None, batched: bool = False,
                         axes: Optional[Tuple[str, ...]] = None,
                         dp: Optional[int] = None):
        """The 'fused_wire' plan: one mega-kernel launch per leaf produces
        (v', g', wire) with g' = g + decode(wire) integrated in-kernel, then
        the wire aggregates under the dense-payload rule. ``grads``/``state``
        leaves are client-local (shard_map, ``batched=False``) or carry a
        leading client axis (vmap runtimes, ``batched=True`` — clients become
        extra tile rows; no vmap-of-pallas_call is ever emitted).
        Returns (msg_mean_tree, new_state)."""
        from repro.kernels import fused_round as fr
        from repro.kernels import ref as kref
        from repro.kernels.backend import interpret

        comp = method.compressor
        if method.name == "ef21_sgd":
            eta_f = 1.0                                  # v' = grad exactly
            v_tree = state["g"]
        else:
            eta_f = float(eta) if eta is not None else float(method.eta)
            v_tree = state["v"]
        interp = interpret()

        g_leaves, treedef = jax.tree_util.tree_flatten(state["g"])
        v_leaves = jax.tree_util.tree_leaves(v_tree)
        grad_leaves = jax.tree_util.tree_leaves(grads)

        # the pads, the launch and the client state under EF_CLIENT; the
        # decode and the mean over clients under EF_AGGREGATE
        v_out, g_out, msg_out = [], [], []
        for grad, v, g in zip(grad_leaves, v_leaves, g_leaves):
            if batched:
                # pad each client's leaf to whole launch blocks FIRST so
                # client boundaries and tile-row boundaries coincide
                dpn = grad.shape[0]
                d = grad[0].size
                nb, block, kb = self._fused_geom(comp, d)
                pad = nb * block - d

                def prep(x):
                    return jnp.pad(x.reshape(dpn, d), ((0, 0), (0, pad)))

                with jax.named_scope(EF_CLIENT):
                    v2, g2, q, scales = fr.ef21_sgdm_topk_quant(
                        prep(grad), prep(v), prep(g), eta=eta_f,
                        block=block, k=kb, bits=self.bits, interpret=interp)
                    v2 = v2[:, :d].reshape(grad.shape)
                    g2 = g2[:, :d].reshape(grad.shape)
                with jax.named_scope(EF_AGGREGATE):
                    vals = kref.block_dequantize_ref(
                        q, scales, bits=self.bits, cols=block)
                    msg = (client_mean(vals, dpn, d)
                           .reshape(grad.shape[1:]).astype(grad.dtype))
            else:
                d = grad.size
                nb, block, kb = self._fused_geom(comp, d)
                with jax.named_scope(EF_CLIENT):
                    v2, g2, q, scales = fr.ef21_sgdm_topk_quant(
                        grad, v, g, eta=eta_f, block=block, k=kb,
                        bits=self.bits, interpret=interp)
                with jax.named_scope(EF_AGGREGATE):
                    vals = kref.block_dequantize_ref(
                        q, scales, bits=self.bits, cols=block)
                    dec = vals.reshape(-1)[:d].astype(jnp.float32)
                    msg = (pmean(dec, axes)  # dense-payload rule:
                           .reshape(grad.shape)      # dequant THEN psum
                           .astype(grad.dtype))
            v_out.append(v2)
            g_out.append(g2)
            msg_out.append(msg)

        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)  # noqa: E731
        msg_mean = unf(msg_out)
        with jax.named_scope(EF_CLIENT):
            g_new = method._cast(unf(g_out))
            if method.name == "ef21_sgd":
                new_state = {"g": g_new}
            else:
                new_state = {"v": method._cast(unf(v_out)), "g": g_new}
        return msg_mean, new_state


# ---------------------------------------------------------------------------
# shared per-leaf dispatch for the 'wire' plan (used by every runtime)
# ---------------------------------------------------------------------------

def wire_round_batched(carrier: Carrier, comp, deltas: PyTree, dp: int
                       ) -> Tuple[PyTree, PyTree]:
    """encode → local_c → aggregate per leaf, clients on a leading axis (vmap
    runtimes). Returns (c_tree, msg_mean_tree); the encode runs under
    EF_CLIENT, the aggregation under EF_AGGREGATE."""
    dleaves, dtree = jax.tree_util.tree_flatten(deltas)
    c_leaves, agg_leaves = [], []
    for leaf in dleaves:
        d = int(leaf[0].size)
        flat = leaf.reshape(dp, d)
        with jax.named_scope(EF_CLIENT):
            wire = jax.vmap(lambda x: carrier.encode(comp, x))(flat)
            c_loc = jax.vmap(lambda x, w: carrier.local_c(comp, x, w))(
                flat, wire)
        with jax.named_scope(EF_AGGREGATE):
            agg = carrier.aggregate(comp, wire, d=d, dtype=leaf.dtype, dp=dp)
        c_leaves.append(c_loc.reshape(leaf.shape))
        agg_leaves.append(agg.reshape(leaf.shape[1:]))
    return (jax.tree_util.tree_unflatten(dtree, c_leaves),
            jax.tree_util.tree_unflatten(dtree, agg_leaves))


def wire_round_local(carrier: Carrier, comp, deltas: PyTree,
                     axes: Tuple[str, ...], rng=None) -> Tuple[PyTree, PyTree]:
    """encode → local_c → aggregate per leaf, client-local inside shard_map
    (explicit named-axis collectives). Returns (c_tree, msg_mean_tree);
    the encode runs under EF_CLIENT, the aggregation under EF_AGGREGATE."""
    dleaves, dtree = jax.tree_util.tree_flatten(deltas)
    c_leaves, agg_leaves = [], []
    for leaf in dleaves:
        flat = leaf.reshape(-1)
        with jax.named_scope(EF_CLIENT):
            wire = carrier.encode_local(comp, flat, rng)
            c_leaves.append(
                carrier.local_c(comp, flat, wire).reshape(leaf.shape))
        with jax.named_scope(EF_AGGREGATE):
            agg_leaves.append(carrier.aggregate(
                comp, wire, d=leaf.size, dtype=leaf.dtype, axes=axes)
                .reshape(leaf.shape))
    return (jax.tree_util.tree_unflatten(dtree, c_leaves),
            jax.tree_util.tree_unflatten(dtree, agg_leaves))


# ---------------------------------------------------------------------------
# downlink (server → client broadcast) — shared by every runtime
# ---------------------------------------------------------------------------

def downlink_round(carrier: Carrier, comp, delta: PyTree,
                   rng: Optional[jax.Array] = None) -> PyTree:
    """One downlink broadcast leg, per leaf: the server encodes C(delta) into
    the carrier's wire and every client returns the decode — which is also
    exactly what the server adds to its own broadcast memory h, so server and
    clients provably hold identical reconstructions (the decode IS the wire;
    there is nothing client-specific to diverge on). Aggregation is a no-op:
    one server, one message, nothing to mean over. On the degraded 'dense'
    plan the broadcast ships the dense C(delta) tensor itself.

    The pure-jnp ``encode`` runs on every runtime (never ``encode_local``):
    the broadcast is one unbatched message, and keeping both runtimes on
    one code path is what makes the round-trip state-sync tests bit-exact
    across them."""
    plan = carrier.plan_down(comp)
    leaves, treedef = jax.tree_util.tree_flatten(delta)
    out = []
    for i, leaf in enumerate(leaves):
        flat = leaf.reshape(-1)
        r = None if rng is None else jax.random.fold_in(rng, i)
        if plan == "wire":
            wire = carrier.encode(comp, flat, r)
            dec = carrier.decode(comp, wire, d=flat.size, dtype=flat.dtype)
        else:
            dec = comp(flat, r).astype(flat.dtype)
        out.append(dec.reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def downlink_encode(carrier: Carrier, comp, delta: PyTree,
                    rng: Optional[jax.Array] = None) -> List:
    """The per-leaf WIRES of one downlink broadcast — the exact payload the
    server puts on the wire (and what core/stream.py persists for serving
    replicas). Per leaf ``i`` the rng is ``fold_in(rng, i)``; on the 'wire'
    plan the payload is ``carrier.encode(C(delta))``, on the degraded 'dense'
    plan it is the dense C(delta) tensor itself. This is the single encode
    path: ``downlink_round_integrate`` (the in-step trainer leg) and the
    stream publisher both call it, so a published record is the same bits the
    trainer integrated."""
    plan = carrier.plan_down(comp)
    leaves = jax.tree_util.tree_leaves(delta)
    wires = []
    for i, leaf in enumerate(leaves):
        flat = leaf.reshape(-1)
        r = None if rng is None else jax.random.fold_in(rng, i)
        if plan == "wire":
            wires.append(carrier.encode(comp, flat, r))
        else:
            wires.append(comp(flat, r).astype(flat.dtype))
    return wires


def downlink_apply(carrier: Carrier, comp, wires: List, h: PyTree) -> PyTree:
    """h' = h + decode(wire), per leaf — the integration EVERY subscriber of
    the broadcast runs: the trainer inside its jitted step, and serving
    replicas between request batches (core/stream.py). Dispatched through
    ``Carrier.decode_add`` so quantized wires can run the one-launch
    dequantize+add Pallas kernel on TPU; the default decode_add IS
    ``h + decode(wire)``, so all consumers agree bit-exactly off-TPU."""
    plan = carrier.plan_down(comp)
    h_leaves, treedef = jax.tree_util.tree_flatten(h)
    out = []
    for wire, hl in zip(wires, h_leaves):
        flat_h = hl.reshape(-1)
        if plan == "wire":
            new = carrier.decode_add(comp, wire, flat_h,
                                     d=flat_h.size, dtype=hl.dtype)
        else:
            new = flat_h + wire.astype(hl.dtype)
        out.append(new.reshape(hl.shape).astype(hl.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def downlink_round_integrate(carrier: Carrier, comp, delta: PyTree,
                             h: PyTree, rng: Optional[jax.Array] = None
                             ) -> PyTree:
    """One downlink broadcast leg WITH the h-integration fused in:
    ``downlink_apply(downlink_encode(delta))`` — encode and integration live
    in those two helpers so the stream publisher/replicas (core/stream.py)
    run literally the same code as this in-step leg. Bit-compatible with
    ``tree_add(h, downlink_round(...))`` (decode_add defaults to that
    expression; the TPU kernel path stays within float-compilation
    tolerance). Same encode/rng discipline as ``downlink_round`` — the wire
    that travels is identical."""
    wires = downlink_encode(carrier, comp, delta, rng)
    return downlink_apply(carrier, comp, wires, h)


def downlink_words(carrier: Carrier, comp, d: int) -> float:
    """Words the server puts on the wire per broadcast message of dimension
    d — the downlink twin of ``Carrier.wire_words`` (the degraded dense plan
    ships the dense d-word tensor)."""
    if carrier.plan_down(comp) == "wire":
        return carrier.wire_words(comp, d)
    return float(d)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def _quant8() -> "QuantCarrier":
    return QuantCarrier(name="quant8", bits=8)


def _quant4() -> "QuantCarrier":
    return QuantCarrier(name="quant4", bits=4)


def _fused_quant8() -> "FusedQuantCarrier":
    return FusedQuantCarrier(name="fused_quant8", bits=8)


def _fused_quant4() -> "FusedQuantCarrier":
    return FusedQuantCarrier(name="fused_quant4", bits=4)


REGISTRY = {
    "dense": DenseCarrier,
    "sparse": SparseBlockCarrier,
    "fused": FusedPallasCarrier,
    "quant8": _quant8,
    "quant4": _quant4,
    "fused_quant8": _fused_quant8,
    "fused_quant4": _fused_quant4,
}


def make(name) -> Carrier:
    if isinstance(name, Carrier):
        return name
    if name not in REGISTRY:
        raise ValueError(f"unknown carrier {name!r}; have {sorted(REGISTRY)}")
    return REGISTRY[name]()
