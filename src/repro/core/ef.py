"""Error-feedback methods from the paper, as functional pytree transforms.

Every method is a pair ``(init, update)``:

  state  = method.init(params_like, init_grads=None)       # per-CLIENT state
  msg, state' = method.update(grads, state, rng)           # one client step

``msg`` is the vector the client transmits. The server/aggregation rule is given by
``method.mode``:

  'delta'    : the server maintains gᵗ and applies   gᵗ⁺¹ = gᵗ + meanᵢ(msgᵢ)
               (EF21 family — msg is the compressed innovation cᵢ; Algorithm 1 line 10)
  'absolute' : the server uses                        gᵗ⁺¹ = meanᵢ(msgᵢ)
               (EF14 / SGD / SGDM — msg is the full local estimate)

The model update is then ``x ← x − γ·gᵗ⁺¹`` (launch/train.py composes this with a full
optimizer; benchmarks use the paper's plain step).

Two-phase decomposition
-----------------------
``update`` factors as  pre_compress → C(·) → post_compress.  The distributed runtime
(core/distributed.py) exploits this to swap the compression carrier (dense tensor vs
fixed-K (values, indices)) and to fuse the whole client update into a single Pallas
kernel (kernels/ef_update.py) without touching method semantics.

Paper ↔ code map
----------------
  EF21-SGD        (5a)+(5ab)              → EF21SGD
  EF21-SGDM       Algorithm 1             → EF21SGDM
  EF21-SGD2M      Algorithm 3 / eq (10)   → EF21SGD2M
  EF21-SGDM (abs) Algorithm 4             → EF21SGDMAbs
  EF21-STORM/MVR  Algorithm 5 / eq (12)   → EF21STORM     (paired-noise gradients)
  EF14-SGD        eq (64)–(65)            → EF14SGD
  SGDM            eq (3) / Appendix J     → SGDM (== EF21SGDM with Identity)
  NEOLITHIC       [Huang et al., 2022]    → Neolithic (R residual-compression rounds)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import compressors as comp_lib

PyTree = Any


# ---------------------------------------------------------------------------
# pytree helpers
# ---------------------------------------------------------------------------

def tree_rngs(rng: jax.Array, tree: PyTree) -> PyTree:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(keys))


def tree_compress(comp: comp_lib.Compressor, tree: PyTree, rng: Optional[jax.Array]) -> PyTree:
    """Apply a flat-vector compressor leaf-wise (K budget ∝ leaf size)."""
    if rng is None:
        return jax.tree_util.tree_map(
            lambda x: comp(x.reshape(-1)).reshape(x.shape), tree)
    rngs = tree_rngs(rng, tree)
    return jax.tree_util.tree_map(
        lambda x, k: comp(x.reshape(-1), k).reshape(x.shape), tree, rngs)


def tree_zeros_like(tree: PyTree, dtype=None) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(a, s):
    return jax.tree_util.tree_map(lambda x: x * s, a)


def tree_lerp(a, b, eta):
    """(1-eta)*a + eta*b — the Polyak momentum update, leaf-wise.

    Written as a + eta*(b - a): ONE product, so a backend that contracts a
    multiply-add into an FMA has only one way to do it. The two-product
    form let XLA's CPU backend pick a different product to keep unrounded
    in each fusion that recomputed the update, so the masked participation
    round stored a momentum 1 ulp off the unmasked one. The Pallas kernels
    and their oracles (kernels/ef_update.py, fused_round.py, ref.py) use
    the same form, so the fused carriers keep dense's exact momentum."""
    def one(x, y):
        xf = x.astype(jnp.float32)
        return (xf + eta * (y.astype(jnp.float32) - xf)).astype(x.dtype)
    return jax.tree_util.tree_map(one, a, b)


def tree_dim(tree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def tree_norm_sq(tree) -> jax.Array:
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
               for x in jax.tree_util.tree_leaves(tree))


def tree_cast(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


# ---------------------------------------------------------------------------
# method base
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Method:
    """Base EF method. Frozen dataclass → usable as a jit static argument."""

    compressor: comp_lib.Compressor = comp_lib.Identity()
    state_dtype: Optional[Any] = None   # None → follow grads; jnp.bfloat16 at LLM scale

    name: str = "base"
    mode: str = "delta"            # 'delta' | 'absolute'
    needs_paired_grads: bool = False
    # True iff the transmitted message IS the compressed tensor c (post_compress
    # returns c unchanged) — the condition for non-dense carriers to aggregate
    # the wire directly (core/carriers.py). False for methods whose message is a
    # transform of c (Abs scaling) or that bypass the two-phase API entirely.
    wire_is_msg: bool = True

    # -- client: two-phase API ----------------------------------------------
    def init(self, params_like: PyTree, init_grads: Optional[PyTree] = None) -> Dict:
        raise NotImplementedError

    def pre_compress(self, grads: PyTree, state: Dict, *, eta=None
                     ) -> Tuple[PyTree, Dict]:
        """→ (delta_to_compress, ctx)."""
        raise NotImplementedError

    def post_compress(self, c: PyTree, ctx: Dict) -> Tuple[PyTree, Dict]:
        """→ (msg, new_state)."""
        raise NotImplementedError

    def update(self, grads: PyTree, state: Dict, rng: Optional[jax.Array] = None,
               *, eta=None, **kw) -> Tuple[PyTree, Dict]:
        delta, ctx = self.pre_compress(grads, state, eta=eta)
        c = tree_compress(self.compressor, delta, rng)
        return self.post_compress(c, ctx)

    # -- accounting (paper plots use "# transmitted coordinates") -----------
    def coords_per_message(self, d: int, carrier=None, direction: str = "up",
                           compressor=None) -> float:
        """Idealized transmitted-coordinate count (paper x-axes) when
        ``carrier`` is None; otherwise delegates to ``Carrier.wire_words`` —
        the honest word count of the actual wire format (dense all-reduce
        ships d words even for a sparse-valued c; the sparse carrier ships
        values AND indices). ``direction='down'`` counts the server
        broadcast instead (``carriers.downlink_words``: one message, no
        aggregation, dense d words on a degraded plan); pass ``compressor``
        to account a downlink compressor different from the uplink one."""
        comp = compressor if compressor is not None else self.compressor
        if direction == "down":
            from repro.core import carriers as carrier_lib
            car = carrier_lib.make(carrier if carrier is not None else "dense")
            return carrier_lib.downlink_words(car, comp, d)
        if carrier is not None:
            from repro.core import carriers as carrier_lib
            return carrier_lib.make(carrier).wire_words(comp, d)
        c = comp
        if isinstance(c, comp_lib.TopK):
            return c._k(d)
        if isinstance(c, comp_lib.RandK):
            return c._k(d)
        if isinstance(c, comp_lib.BlockTopK):
            nb, _, kb = c.geom(d)       # d-aware: sub-block leaves keep K ≤ d
            return nb * kb
        if isinstance(c, comp_lib.NaturalCompression):
            return d * 9.0 / 32.0
        if isinstance(c, comp_lib.HardThreshold):
            return d  # data-dependent; upper bound
        return d

    def coords_per_message_tree(self, tree, schedule=None, carrier=None,
                                direction: str = "up", compressor=None,
                                eta=None) -> float:
        """The pytree/schedule form of ``coords_per_message``, summed over
        groups with per-leaf geometry. The units follow the flat-d form
        exactly: no ``carrier`` → the idealized transmitted-coordinate count
        (the paper's x-axis; ``direction='down'`` counts the broadcast
        words, as flat-d does); with a schedule each group already names its
        own carrier, so passing ``carrier``/``compressor`` alongside one is
        an error rather than silently ignored — for the honest executed
        wire-word sums use ``schedule.wire_words_tree`` directly. Without a
        schedule this collapses to the flat-d form over the whole tree."""
        if schedule is None:
            return self.coords_per_message(tree_dim(tree), carrier, direction,
                                           compressor)
        if carrier is not None or compressor is not None:
            raise ValueError(
                "coords_per_message_tree: with a schedule every group names "
                "its own carrier/compressor — pass only the schedule (use "
                "schedule.wire_words_tree for executed wire-word sums)")
        from repro.core import schedule as sched_lib
        if direction == "down":
            _, total = sched_lib.wire_words_tree(schedule, self, tree,
                                                 direction="down", eta=eta)
            return total
        return sched_lib.coords_tree(schedule, self, tree)

    def _cast(self, tree):
        return tree_cast(tree, self.state_dtype)

    def _eta(self, eta):
        if eta is not None:
            return eta
        return getattr(self, "eta", 1.0)


@dataclasses.dataclass(frozen=True)
class EF21SGD(Method):
    """EF21 with (mini/mega-batch) stochastic gradients — eq (5a)+(5ab).

    The paper proves (Thm 1, idealized; Figs 1 & 4 empirically) that this method
    fails near stationarity unless B = Ω(σ²/ε²).
    """
    name: str = "ef21_sgd"
    mode: str = "delta"

    def init(self, params_like, init_grads=None):
        g = init_grads if init_grads is not None else tree_zeros_like(params_like)
        return {"g": self._cast(g)}

    def pre_compress(self, grads, state, *, eta=None):
        return tree_sub(grads, state["g"]), {"g": state["g"]}

    def post_compress(self, c, ctx):
        g_new = tree_add(ctx["g"], c)
        return c, {"g": self._cast(g_new)}


@dataclasses.dataclass(frozen=True)
class EF21SGDM(Method):
    """EF21-SGDM — **Algorithm 1**, the paper's main contribution.

      vᵗ⁺¹ = (1−η)vᵗ + η ∇f(xᵗ⁺¹, ξ)      (client momentum, line 6)
      cᵗ⁺¹ = C(vᵗ⁺¹ − gᵗ)                  (line 7)
      gᵗ⁺¹ = gᵗ + cᵗ⁺¹                     (line 8)

    Theorem 3: batch-free, no BG/BGS, asymptotically optimal O(σ²/(nε⁴)) samples.
    """
    eta: float = 0.1
    name: str = "ef21_sgdm"
    mode: str = "delta"

    def init(self, params_like, init_grads=None):
        v = init_grads if init_grads is not None else tree_zeros_like(params_like)
        return {"v": self._cast(v), "g": self._cast(v)}

    def pre_compress(self, grads, state, *, eta=None):
        v_new = tree_lerp(state["v"], grads, self._eta(eta))
        return tree_sub(v_new, state["g"]), {"v": v_new, "g": state["g"]}

    def post_compress(self, c, ctx):
        g_new = tree_add(ctx["g"], c)
        return c, {"v": self._cast(ctx["v"]), "g": self._cast(g_new)}


@dataclasses.dataclass(frozen=True)
class EF21SGD2M(Method):
    """EF21-SGD2M — **Algorithm 3** (double momentum, eq (10)).

      vᵗ⁺¹ = (1−η)vᵗ + η ∇f(xᵗ⁺¹, ξ);  uᵗ⁺¹ = (1−η)uᵗ + η vᵗ⁺¹;  c = C(uᵗ⁺¹ − gᵗ)

    Corollary 3: removes the O(α^{-1/2}ε^{-3}) middle complexity term.
    """
    eta: float = 0.1
    name: str = "ef21_sgd2m"
    mode: str = "delta"

    def init(self, params_like, init_grads=None):
        v = init_grads if init_grads is not None else tree_zeros_like(params_like)
        return {"v": self._cast(v), "u": self._cast(v), "g": self._cast(v)}

    def pre_compress(self, grads, state, *, eta=None):
        e = self._eta(eta)
        v_new = tree_lerp(state["v"], grads, e)
        u_new = tree_lerp(state["u"], v_new, e)
        return tree_sub(u_new, state["g"]), \
            {"v": v_new, "u": u_new, "g": state["g"]}

    def post_compress(self, c, ctx):
        g_new = tree_add(ctx["g"], c)
        return c, {"v": self._cast(ctx["v"]), "u": self._cast(ctx["u"]),
                   "g": self._cast(g_new)}


@dataclasses.dataclass(frozen=True)
class EF21SGDMIdeal(Method):
    """EF21-SGDM-ideal — eq (14)+(15), the *conceptual* method of Theorem 4
    (η=1 gives EF21-SGD-ideal, eq (5a)+(5aa), Theorem 1).

      gᵢᵗ⁺¹ = ∇fᵢ(xᵗ⁺¹) + C(η·(∇fᵢ(xᵗ⁺¹, ξ) − ∇fᵢ(xᵗ⁺¹)))

    Requires exact gradients (not implementable at paper-scale by design —
    used for the Theorem 1 lower-bound reproduction): ``update`` takes
    ``grads=(stoch_grad, exact_grad)``.
    """
    eta: float = 1.0
    name: str = "ef21_sgdm_ideal"
    mode: str = "absolute"          # server uses gᵗ = meanᵢ gᵢᵗ directly
    needs_paired_grads: bool = True  # (stochastic, exact) pair
    wire_is_msg: bool = False        # msg = ∇fᵢ + c, not c (no two-phase API)

    def init(self, params_like, init_grads=None):
        return {}

    def update(self, grads, state, rng=None, *, eta=None, **kw):
        e = self._eta(eta)
        g_stoch, g_exact = grads
        noise = tree_scale(tree_sub(g_stoch, g_exact), e)
        c = tree_compress(self.compressor, noise, rng)
        return tree_add(g_exact, c), state


@dataclasses.dataclass(frozen=True)
class EF21SGDMAbs(Method):
    """EF21-SGDM with an *absolute* compressor — **Algorithm 4**.

    The innovation is scaled by 1/γ before compression and by γ after, so the
    absolute error Δ enters the rate as γ²Δ² (Theorem 6):
        cᵗ⁺¹ = γ·C((vᵗ⁺¹ − gᵗ)/γ)
    """
    eta: float = 0.1
    gamma: float = 1e-2
    name: str = "ef21_sgdm_abs"
    mode: str = "delta"
    wire_is_msg: bool = False        # msg = γ·c — a transform of the wire

    def init(self, params_like, init_grads=None):
        v = init_grads if init_grads is not None else tree_zeros_like(params_like)
        return {"v": self._cast(v), "g": self._cast(v)}

    def pre_compress(self, grads, state, *, eta=None):
        v_new = tree_lerp(state["v"], grads, self._eta(eta))
        innov = tree_scale(tree_sub(v_new, state["g"]), 1.0 / self.gamma)
        return innov, {"v": v_new, "g": state["g"]}

    def post_compress(self, c, ctx):
        c = tree_scale(c, self.gamma)
        g_new = tree_add(ctx["g"], c)
        return c, {"v": self._cast(ctx["v"]), "g": self._cast(g_new)}


@dataclasses.dataclass(frozen=True)
class EF21STORM(Method):
    """EF21-STORM/MVR — **Algorithm 5** (variance-reduced estimator, eq (12)).

      wᵗ⁺¹ = ∇f(xᵗ⁺¹, ξᵗ⁺¹) + (1−η)(wᵗ − ∇f(xᵗ, ξᵗ⁺¹))

    Requires TWO stochastic gradients under the SAME noise ξᵗ⁺¹ (the paper flags
    this as a practical limitation, App. B): update takes ``grads=(g_new, g_prev)``.
    """
    eta: float = 0.1
    name: str = "ef21_storm"
    mode: str = "delta"
    needs_paired_grads: bool = True

    def init(self, params_like, init_grads=None):
        w = init_grads if init_grads is not None else tree_zeros_like(params_like)
        return {"w": self._cast(w), "g": self._cast(w)}

    def pre_compress(self, grads, state, *, eta=None):
        e = self._eta(eta)
        g_new, g_prev = grads
        w_new = tree_add(g_new, tree_scale(tree_sub(state["w"], g_prev), 1.0 - e))
        return tree_sub(w_new, state["g"]), {"w": w_new, "g": state["g"]}

    def post_compress(self, c, ctx):
        g_out = tree_add(ctx["g"], c)
        return c, {"w": self._cast(ctx["w"]), "g": self._cast(g_out)}


@dataclasses.dataclass(frozen=True)
class EF14SGD(Method):
    """EF14-SGD [Seide et al., 2014] — eq (64)–(65), in gradient units.

    pᵗ = eᵗ + ∇f(xᵗ, ξ);  msg = C(pᵗ);  eᵗ⁺¹ = pᵗ − msg.
    For a constant step size this is exactly (64)–(65) with e and g divided by γ
    (the standard implementation form, cf. Karimireddy et al. 2019).
    """
    name: str = "ef14_sgd"
    mode: str = "absolute"

    def init(self, params_like, init_grads=None):
        return {"e": self._cast(tree_zeros_like(params_like))}

    def pre_compress(self, grads, state, *, eta=None):
        p = tree_add(state["e"], grads)
        return p, {"p": p}

    def post_compress(self, c, ctx):
        e_new = tree_sub(ctx["p"], c)
        return c, {"e": self._cast(e_new)}


@dataclasses.dataclass(frozen=True)
class SGDM(Method):
    """Plain Polyak SGDM — eq (3); analyzed untuned in Appendix J. No compression."""
    eta: float = 0.1
    name: str = "sgdm"
    mode: str = "absolute"

    def init(self, params_like, init_grads=None):
        v = init_grads if init_grads is not None else tree_zeros_like(params_like)
        return {"v": self._cast(v)}

    def pre_compress(self, grads, state, *, eta=None):
        v_new = tree_lerp(state["v"], grads, self._eta(eta))
        return v_new, {"v": v_new}

    def post_compress(self, c, ctx):
        return c, {"v": self._cast(ctx["v"])}


@dataclasses.dataclass(frozen=True)
class SGD(Method):
    """Uncompressed distributed SGD (reference)."""
    name: str = "sgd"
    mode: str = "absolute"

    def init(self, params_like, init_grads=None):
        return {}

    def pre_compress(self, grads, state, *, eta=None):
        return grads, {}

    def post_compress(self, c, ctx):
        return c, {}


@dataclasses.dataclass(frozen=True)
class Neolithic(Method):
    """NEOLITHIC-style baseline [Huang et al., 2022]: R rounds of residual
    compression per iteration (R = ⌈d/K⌉ per their Thm 3 → effectively transmits
    every coordinate, which is why the paper's Fig 2 shows it losing per-bit)."""
    rounds: int = 4
    name: str = "neolithic"
    mode: str = "absolute"
    wire_is_msg: bool = False        # R-round accumulator, no two-phase API

    def init(self, params_like, init_grads=None):
        return {}

    def update(self, grads, state, rng=None, **kw):
        acc = tree_zeros_like(grads)
        resid = grads
        for r in range(self.rounds):
            k = None if rng is None else jax.random.fold_in(rng, r)
            c = tree_compress(self.compressor, resid, k)
            acc = tree_add(acc, c)
            resid = tree_sub(resid, c)
        return acc, state

    def coords_per_message(self, d: int, carrier=None, direction: str = "up",
                           compressor=None) -> float:
        base = super().coords_per_message(d, carrier, direction, compressor)
        if direction == "down":
            return base     # one broadcast regardless of the R uplink rounds
        return self.rounds * base


# ---------------------------------------------------------------------------
# server-side aggregation
# ---------------------------------------------------------------------------

def server_init(method: Method, params_like: PyTree,
                init_grads_mean: Optional[PyTree] = None) -> PyTree:
    """The aggregated estimate gᵗ the server maintains (g⁰ = (1/n)Σ gᵢ⁰)."""
    if method.mode == "delta":
        g = init_grads_mean if init_grads_mean is not None \
            else tree_zeros_like(params_like)
        return g
    return tree_zeros_like(params_like)


def server_step(method: Method, g_server: PyTree, msg_mean: PyTree) -> PyTree:
    if method.mode == "delta":
        return tree_add(g_server, msg_mean)
    return msg_mean


# ---------------------------------------------------------------------------
# downlink: the server → client broadcast leg (bidirectional compression)
# ---------------------------------------------------------------------------

def downlink_init(g_server: PyTree) -> PyTree:
    """h⁰ — the server's EF21 broadcast memory, initialized to g⁰ (the init
    handshake already ships dense state once: params, and under Alg 1 line 2
    the g⁰ mean — so server and clients agree on h⁰ exactly). Works the same
    for both server modes: h tracks whatever estimate ``server_step``
    produces ('delta' methods integrate messages into g; 'absolute' methods
    replace it), because the downlink contraction argument only needs the
    broadcast target, never the method semantics."""
    return g_server


def downlink_sync(carrier, comp, g_server: PyTree, h: Optional[PyTree],
                  rng: Optional[jax.Array] = None, memory: bool = True
                  ) -> Tuple[PyTree, Optional[PyTree]]:
    """One downlink broadcast: returns ``(g_est, h_new)`` where ``g_est`` is
    the estimate every client (and the server) steps the model with.

    With ``memory`` (EF21-BC, Fatkhullin et al. 2021): the server broadcasts
    the wire of C(g − h) and everyone integrates the decode,
    hᵗ⁺¹ = hᵗ + decode(wire) — so g_est = hᵗ⁺¹ is bit-identical on server and
    clients, and the compression error is re-sent in later rounds (the same
    contraction that makes uplink EF21 work). Without ``memory`` (the naive
    baseline the paper-claims tests stall): the broadcast is C(g) itself each
    round, nothing absorbs the compression error, and ``h_new`` is None."""
    from repro.core import carriers as carrier_lib
    if not memory:
        return carrier_lib.downlink_round(carrier, comp, g_server, rng), None
    # decode + h-integration in one fused leg (downlink_round_integrate):
    # quantized wires run the one-launch dequantize+add kernel on TPU;
    # everywhere else this is exactly h + decode(wire)
    h_new = carrier_lib.downlink_round_integrate(
        carrier, comp, tree_sub(g_server, h), h, rng)
    return h_new, h_new


REGISTRY = {
    "ef21_sgdm_ideal": EF21SGDMIdeal,
    "ef21_sgd": EF21SGD,
    "ef21_sgdm": EF21SGDM,
    "ef21_sgd2m": EF21SGD2M,
    "ef21_sgdm_abs": EF21SGDMAbs,
    "ef21_storm": EF21STORM,
    "ef14_sgd": EF14SGD,
    "sgdm": SGDM,
    "sgd": SGD,
    "neolithic": Neolithic,
}


def make(name: str, **kwargs) -> Method:
    if name not in REGISTRY:
        raise ValueError(f"unknown EF method {name!r}; have {sorted(REGISTRY)}")
    return REGISTRY[name](**kwargs)
