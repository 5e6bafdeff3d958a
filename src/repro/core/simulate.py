"""n-client distributed-training simulator (Algorithm 1's outer loop).

This is the exact loop of Algorithm 1 / 3 / 4 / 5 (and the EF14/SGD baselines) run
over an arbitrary :class:`repro.core.problems.Problem`, with all n clients carried as
a leading axis and stepped by ``vmap`` — a faithful single-host emulation of the
distributed method that the paper's own experiments use. It steps the vmap
round of core/distributed.py: each scan step draws the clients' gradients,
then runs ``uplink_round`` and ``downlink_leg``, so what is validated here is the
round the production path runs. :class:`SimConfig` names the round's settings
(carrier, downlink, schedule, participation, hops) as ``EFConfig`` does
(:meth:`SimConfig.ef_config`); on top of the round the simulator owns the step
sizes (constant or time-varying γₜ, ηₜ), the ``down_memory=False``
naive-broadcast ablation the paper-claims tests use, and the wire accounting.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import carriers as carrier_lib
from repro.core import distributed as dist
from repro.core import ef as ef_lib
from repro.core import hierarchy as hier_lib
from repro.core import participation as part_lib
from repro.core import schedule as sched_lib

PyTree = Any


@dataclasses.dataclass(frozen=True)
class SimConfig:
    n: int = 1                      # number of clients
    batch_size: int = 1             # per-client minibatch B
    gamma: float = 1e-3             # step size γ
    eta: Optional[float] = None     # momentum η override (None → method default)
    steps: int = 1000               # T
    b_init: int = 1                 # initial batch size B_init (Alg 1 line 2)
    time_varying: bool = False      # γₜ = γ/√(t+1), ηₜ = η/√(t+1) (App. J / Fig 4)
    record_every: int = 1
    carrier: str = "dense"     # any core/carriers.py REGISTRY name:
    # 'dense'|'sparse'|'fused'|'quant8'|'quant4'|'fused_quant8'|'fused_quant4'
    # downlink (server → client broadcast) leg, DESIGN.md §8. The default
    # ('dense', no compressor) runs no downlink. ``down_memory=False`` is the
    # NAIVE ablation (broadcast C(g) with no server memory — nothing re-sends
    # the compression error; the paper-claims tests show it stalling).
    down_carrier: str = "dense"
    down_compressor: Optional[Any] = None   # a Compressor (frozen → hashable)
    down_memory: bool = True
    # per-parameter-group compression (DESIGN.md §9): when set, the round and
    # the wire accounting dispatch through the grouped engine in
    # core/schedule.py; the single-knob carrier/down_* fields are ignored.
    schedule: Optional[sched_lib.CompressionSchedule] = None
    # partial participation (DESIGN.md §11): mode='sampled' masks each round
    # to a seeded cohort (the "EF21 with Bells & Whistles" rule).
    # mode='async' never runs here — core/participation.py::run_async is the
    # event-driven simulator.
    participation: Optional[part_lib.Participation] = None
    # two-tier hierarchical aggregation (DESIGN.md §13): clients → pod
    # aggregator → global server, the cross-pod hop on its own carrier and
    # EF memory per pod. None or pods=1 is the flat loop.
    hops: Optional[hier_lib.Hops] = None

    def ef_config(self, method: ef_lib.Method) -> dist.EFConfig:
        """The EFConfig of the round this simulation runs."""
        return dist.EFConfig(
            method=method, carrier=self.carrier,
            down_carrier=self.down_carrier,
            down_compressor=self.down_compressor, schedule=self.schedule,
            participation=self.participation, hops=self.hops)


def _client_rngs(rng, n):
    return jax.random.split(rng, n)


@partial(jax.jit, static_argnames=("problem", "method", "cfg"))
def run(problem, method: ef_lib.Method, cfg: SimConfig, rng: jax.Array) -> Dict:
    """Run T steps; returns per-recorded-step metrics (grad norm², f(x), coords sent).

    problem: frozen dataclass with
        init_x()                          -> pytree x⁰
        stoch_grad(x, client, rng, B)     -> pytree (client ∈ [0, n))
        full_grad(x)                      -> pytree ∇f(x)
        loss(x)                           -> scalar f(x)
    """
    x0 = problem.init_x()
    rng, r_init = jax.random.split(rng)

    clients = jnp.arange(cfg.n)

    def init_grad_one(c, r):
        ks = jax.random.split(r, cfg.b_init)
        gs = jax.vmap(lambda k: problem.stoch_grad(x0, c, k, cfg.batch_size))(ks)
        return jax.tree_util.tree_map(lambda g: g.mean(0), gs)

    g0 = jax.vmap(init_grad_one)(clients, _client_rngs(r_init, cfg.n))
    efc = cfg.ef_config(method)
    has_down = efc.has_downlink
    eta0 = cfg.eta if cfg.eta is not None else getattr(method, "eta", 1.0)

    def step(carry, t):
        x, ef_state, rng = carry
        # g_est is what the clients reconstructed last round: the broadcast
        # memory h (or the latest naive decode), else the server estimate
        g_est = ef_state["h"] if has_down else ef_state["server"]
        rng, r_grad, r_comp = jax.random.split(rng, 3)
        if cfg.time_varying:
            # App. J schedule: γₜ = γ/√(t+1), ηₜ = 1/√(t+1)
            scale = 1.0 / jnp.sqrt(t + 1.0)
            gamma_t = cfg.gamma * scale
            eta_t = jnp.minimum(scale, 1.0)
        else:
            # constant-parameter setting of Theorems 2/3 — η stays a python
            # float so the fused carrier can bake it into the Pallas kernel
            gamma_t, eta_t = cfg.gamma, eta0

        x_next = jax.tree_util.tree_map(lambda p, g: p - gamma_t * g, x, g_est)

        def client_grads(c, rg):
            if method.needs_paired_grads:
                g_new = problem.stoch_grad(x_next, c, rg, cfg.batch_size)
                if method.name == "ef21_sgdm_ideal":
                    exact = getattr(problem, "client_grad",
                                    lambda xx, cc: problem.full_grad(xx))
                    return (g_new, exact(x_next, c))
                # STORM: two stochastic grads under the SAME ξ
                return (g_new, problem.stoch_grad(x, c, rg, cfg.batch_size))
            return problem.stoch_grad(x_next, c, rg, cfg.batch_size)

        grads = jax.vmap(client_grads)(clients, _client_rngs(r_grad, cfg.n))
        g_server, new_state = dist.uplink_round(
            efc, grads, ef_state, r_comp, eta=eta_t, step=t)
        if has_down:
            # the naive broadcast keeps no memory: its decode rides the 'h'
            # slot as next round's g_est
            new_state["h"], _ = dist.downlink_leg(
                efc, g_server, ef_state["h"], r_comp, memory=cfg.down_memory)
        gn = ef_lib.tree_norm_sq(problem.full_grad(x_next))
        fl = problem.loss(x_next)
        return (x_next, new_state, rng), (gn, fl)

    state0 = dist.init_ef_state(efc, x0, cfg.n, init_grads=g0)
    (x_fin, _, _), (gns, fls) = jax.lax.scan(
        step, (x0, state0, rng), jnp.arange(cfg.steps))
    d_total = ef_lib.tree_dim(x0)
    # honest wire accounting follows the plan that actually EXECUTED: when the
    # carrier degrades to the dense plan (unsupported compressor/method,
    # traced ηₜ), what went on the wire was the dense tensor — d words
    eta_static = None if cfg.time_varying else eta0
    part = cfg.participation
    m_cohort = part.cohort_size(cfg.n) \
        if part is not None and part.is_sampling else cfg.n
    # Sampled participation: only the m = cohort_size(fraction·n) sampled
    # clients upload, so the honest uplink budget is per-message words × m
    # (DESIGN.md §11). The downlink broadcast still reaches all n links —
    # that is how absent clients stay in sync with the server memory h.
    if cfg.schedule is not None:
        # per-group accounting (DESIGN.md §9): each group's executed wire,
        # summed over its leaves — exposed per group AND in total
        up_per, up_each = sched_lib.wire_words_tree(
            cfg.schedule, method, x0, "up", eta_static)
        dn_per, dn_each = sched_lib.wire_words_tree(
            cfg.schedule, method, x0, "down", eta_static)
        up_words, down_words = up_each * m_cohort, dn_each * cfg.n
        coords = sched_lib.coords_tree(cfg.schedule, method, x0) * m_cohort
        group_words = {
            "wire_words_up_per_group": tuple(w * m_cohort for w in up_per),
            "wire_words_down_per_group": tuple(w * cfg.n for w in dn_per),
        }
    else:
        executed = cfg.carrier if carrier_lib.make(cfg.carrier).plan(
            method, eta_static) != "dense" else "dense"
        up_words = method.coords_per_message(
            d_total, carrier=executed) * m_cohort
        # downlink: one broadcast message per client link; without a downlink
        # carrier the server ships the dense f32 estimate — d words per client
        down_each = carrier_lib.downlink_words(
            carrier_lib.make(cfg.down_carrier), efc.down_comp(), d_total) \
            if has_down else float(d_total)
        down_words = down_each * cfg.n
        coords = method.coords_per_message(d_total) * m_cohort
        group_words = {}
    # per-hop accounting (DESIGN.md §13): under a flat topology the only
    # client→server hop IS the cross-pod wire (cross := up, intra := 0);
    # under hops the n client messages ride the fast intra-pod links and the
    # slow cross-pod links carry one compressed innovation per pod
    hops = efc.effective_hops
    if hops is None:
        intra_words, cross_words = 0.0, up_words
    else:
        intra_words = up_words
        cross_words = hier_lib.wire_words_cross(hops, cfg.schedule, method,
                                                x0)
    return {
        "grad_norm_sq": gns,
        "loss": fls,
        "x_final": x_fin,
        # paper x-axis: idealized transmitted-coordinate count
        "coords_per_round": coords,
        # honest word count of the executed wire (values + indices; dense
        # all-reduce ships d) — see Carrier.wire_words. The legacy key is
        # the UPLINK leg; the split keys make the total wire budget per
        # round (the paper's communication-complexity story) explicit.
        "wire_words_per_round": up_words,
        "wire_words_up_per_round": up_words,
        "wire_words_down_per_round": down_words,
        "wire_words_total_per_round": intra_words + cross_words + down_words,
        # per-hop split (DESIGN.md §13): intra = per-message words × n
        # clients, cross = per-pod innovation words × pods
        "wire_words_intra_per_round": intra_words,
        "wire_words_cross_per_round": cross_words,
        **group_words,
    }


def run_numpy(problem, method, cfg: SimConfig, seed: int = 0) -> Dict:
    """Convenience wrapper returning numpy arrays."""
    out = run(problem, method, cfg, jax.random.PRNGKey(seed))
    return {k: jax.device_get(v) for k, v in out.items()}
