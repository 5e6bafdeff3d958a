"""Session: the single runtime facade over a RunSpec.

``Session(spec)`` owns everything a run needs — arch config, mesh, ShardPlan,
EFConfig, optimizer, data pipeline, the jitted step, metrics history, and
full-state checkpointing — so drivers, examples, and benchmarks are thin
flag→RunSpec→Session shims with no assembly logic of their own:

    spec = RunSpec(arch="smollm-360m", smoke=True, clients=4)
    sess = Session(spec)
    sess.train(200)                   # EF21-SGDM on the synthetic pipeline
    sess.evaluate()                   # held-out loss at the current params
    sess.serve(batch=4, ...)          # prefill+decode through build_* shardings
    sess.lower("train_4k")            # the dry-run artifact

Checkpointing is FULL-state (DESIGN.md §7): params + opt_state + ef_state +
the data cursor + the RunSpec itself (and its hash) in checkpoint meta.
``Session.resume(dir)`` reconstructs the run without re-passing any flags,
and a resumed run is bit-identical to an uninterrupted one — restoring only
params (the old ``train.py --resume`` behavior) silently violated the EF21
invariant that server and clients agree on g (Algorithm 1 line 8), because a
fresh ef_state re-initializes gᵢ from step-0 gradients while the restored
params are mid-trajectory. ``tests/test_session.py`` proves
save→restore→step equals the uninterrupted trajectory exactly.

EFConfig construction lives behind the spec (``ef_config``/``make_method``
below, delegating to launch/build.py for the authoritative carrier checks);
no driver builds an EFConfig or mesh by hand anymore.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import base as cb
from repro.core import compressors as comp_lib
from repro.core import distributed as dist
from repro.core import ef as ef_lib
from repro.data import pipeline as pipe_lib
from repro.launch import build as build_lib
from repro.launch import mesh as mesh_lib
from repro.launch import shardings as sh
from repro.launch import spec as spec_lib
from repro.models import model as model_lib
from repro.optim import optimizer as opt_lib

PyTree = Any


# ---------------------------------------------------------------------------
# spec → objects factories (the only construction path for method/compressor)
# ---------------------------------------------------------------------------

def make_compressor(spec: spec_lib.RunSpec) -> comp_lib.Compressor:
    """Compressor named by the spec. ``ratio`` flows in only when the class
    has a ratio field (HardThreshold takes ``lam``, NaturalCompression takes
    nothing); ``compressor_kw`` overrides any field explicitly."""
    cls = comp_lib.REGISTRY[spec.compressor]
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = dict(spec.compressor_kw)
    if "ratio" in fields and "ratio" not in kw:
        kw["ratio"] = spec.ratio
    unknown = sorted(set(kw) - fields)
    if unknown:
        raise ValueError(f"compressor_kw keys {unknown} are not fields of "
                         f"{cls.__name__}; have {sorted(fields)}")
    return cls(**kw)


def make_down_compressor(spec: spec_lib.RunSpec
                         ) -> Optional[comp_lib.Compressor]:
    """The DOWNLINK compressor named by the spec: None when
    downlink_carrier='dense' (no downlink machinery — the implicit dense
    broadcast), otherwise the uplink compressor class re-budgeted to
    ``downlink_ratio``. ``compressor_kw`` geometry (block sizes, lam, …)
    carries over, but the absolute-budget keys (k / k_per_block / ratio) are
    dropped so downlink_ratio actually drives the broadcast budget instead of
    being silently shadowed by an uplink override. Like the uplink
    ``ratio``, downlink_ratio only applies to ratio-bearing compressor
    classes — hard_threshold / rank1 / block_quant budgets are set by their
    own compressor_kw knobs, which the downlink reuses unchanged."""
    if spec.downlink_carrier == "dense":
        return None
    cls = comp_lib.REGISTRY[spec.compressor]
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in spec.compressor_kw.items()
          if k in fields and k not in ("k", "k_per_block", "ratio")}
    if "ratio" in fields:
        kw["ratio"] = spec.downlink_ratio
    return cls(**kw)


def _group_compressor(entry: Dict[str, Any]) -> comp_lib.Compressor:
    """Compressor for one RESOLVED group entry (spec_lib.resolved_groups):
    same rules as make_compressor — ratio flows in only when the class has a
    ratio field, compressor_kw overrides explicitly, unknown keys fail."""
    cls = comp_lib.REGISTRY[entry["compressor"]]
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = dict(entry["compressor_kw"])
    if "ratio" in fields and "ratio" not in kw:
        kw["ratio"] = entry["ratio"]
    unknown = sorted(set(kw) - fields)
    if unknown:
        raise ValueError(f"group {entry['pattern']!r}: compressor_kw keys "
                         f"{unknown} are not fields of {cls.__name__}; have "
                         f"{sorted(fields)}")
    return cls(**kw)


def _group_down_compressor(entry: Dict[str, Any]
                           ) -> Optional[comp_lib.Compressor]:
    """The group's downlink compressor: None without a downlink carrier,
    otherwise the group's compressor class re-budgeted to the group's
    downlink_ratio (absolute-budget kwargs dropped — the make_down_compressor
    rule, per group)."""
    if entry["downlink_carrier"] == "dense":
        return None
    cls = comp_lib.REGISTRY[entry["compressor"]]
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in entry["compressor_kw"].items()
          if k in fields and k not in ("k", "k_per_block", "ratio")}
    if "ratio" in fields:
        kw["ratio"] = entry["downlink_ratio"]
    return cls(**kw)


def _group_cross_compressor(entry: Dict[str, Any]
                            ) -> Optional[comp_lib.Compressor]:
    """The group's CROSS-POD compressor: None for a dense cross carrier (the
    trivial cross — the pod target ships exactly), otherwise the group's
    compressor class re-budgeted to the group's cross_ratio
    (absolute-budget kwargs dropped — the make_down_compressor rule, per
    group, applied to the pod→server hop)."""
    if entry["cross_carrier"] == "dense":
        return None
    cls = comp_lib.REGISTRY[entry["compressor"]]
    fields = {f.name for f in dataclasses.fields(cls)}
    kw = {k: v for k, v in entry["compressor_kw"].items()
          if k in fields and k not in ("k", "k_per_block", "ratio")}
    if "ratio" in fields:
        kw["ratio"] = entry["cross_ratio"]
    return cls(**kw)


def make_hops(spec: spec_lib.RunSpec):
    """The two-tier topology named by the spec's ``hops`` (DESIGN.md §13),
    or None when absent / pods == 1 (the flat path — bit-identical, zero
    hierarchical machinery). The cross compressor follows the
    make_down_compressor rule: None for a dense cross carrier, otherwise the
    uplink compressor class re-budgeted to ``cross_ratio`` — the cross hop
    is one message per pod, integrated exactly like a broadcast."""
    h = spec_lib.hops_preview(spec)
    if not h["hierarchical"]:
        return None
    from repro.core import hierarchy as hier_lib
    cross_comp = None
    if h["cross_carrier"] != "dense":
        cls = comp_lib.REGISTRY[spec.compressor]
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in spec.compressor_kw.items()
              if k in fields and k not in ("k", "k_per_block", "ratio")}
        if "ratio" in fields:
            kw["ratio"] = h["cross_ratio"]
        cross_comp = cls(**kw)
    return hier_lib.Hops(pods=h["pods"], cross_carrier=h["cross_carrier"],
                         cross_compressor=cross_comp)


def make_schedule(spec: spec_lib.RunSpec):
    """The CompressionSchedule named by the spec's ``groups``, or None when
    the spec has no explicit groups (the legacy single-compressor path — a
    uniform one-group schedule would be bit-identical, but None keeps the
    regression anchor trivially exact and the state trees byte-stable)."""
    if not spec.groups:
        return None
    from repro.core import schedule as sched_lib
    groups = []
    for entry in spec_lib.resolved_groups(spec):
        groups.append(sched_lib.Group(
            pattern=entry["pattern"],
            compressor=_group_compressor(entry),
            carrier=entry["carrier"],
            down_carrier=entry["downlink_carrier"],
            down_compressor=_group_down_compressor(entry),
            state_dtype=entry["ef_state_dtype"],
            cross_carrier=entry["cross_carrier"],
            cross_compressor=_group_cross_compressor(entry)))
    return sched_lib.CompressionSchedule(tuple(groups))


def make_participation(spec: spec_lib.RunSpec):
    """The Participation named by the spec, or None when the spec has no
    explicit participation (the legacy full-cohort path — a mode='full'
    object would be equivalent, but None keeps the legacy runtimes'
    jaxprs byte-stable)."""
    if not spec.participation:
        return None
    from repro.core import participation as part_lib
    p = spec.participation
    return part_lib.Participation(
        mode=p.get("mode", "full"),
        fraction=float(p.get("fraction", 1.0)),
        seed=int(p.get("seed", 0)))


def make_method(spec: spec_lib.RunSpec) -> ef_lib.Method:
    """EF method named by the spec, usable standalone (simulator examples)
    or via ``ef_config`` on the production path."""
    cls = ef_lib.REGISTRY[spec.method]
    fields = {f.name for f in dataclasses.fields(cls)}
    kw: Dict[str, Any] = {
        "compressor": make_compressor(spec),
        "state_dtype": jnp.bfloat16 if spec.ef_state_dtype == "bfloat16"
        else None,
    }
    # every eta-bearing method gets the spec's eta: the spec records η, so a
    # class default must never run in its place (method_kw still overrides)
    if "eta" in fields:
        kw["eta"] = spec.eta
    kw.update(spec.method_kw)
    unknown = sorted(set(kw) - fields)
    if unknown:
        raise ValueError(f"method_kw keys {unknown} are not fields of "
                         f"{cls.__name__}; have {sorted(fields)}")
    return cls(**kw)


def ef_config(spec: spec_lib.RunSpec, mesh, plan: sh.ShardPlan
              ) -> dist.EFConfig:
    """The EFConfig for this spec on a concrete mesh — the authoritative
    carrier plan check (launch/build.py) runs here, after the spec's own
    jax-free preview already failed fast at construction."""
    return build_lib.default_ef_config(
        mesh, plan, method_name=spec.method, compressor_name=spec.compressor,
        ratio=spec.ratio, eta=spec.eta, carrier=spec.carrier,
        method=make_method(spec), down_carrier=spec.downlink_carrier,
        down_compressor=make_down_compressor(spec),
        schedule=make_schedule(spec), overlap=spec.overlap,
        participation=make_participation(spec), hops=make_hops(spec))


def distributed_init(coordinator: str, num_processes: int,
                     process_id: int) -> bool:
    """Facade re-export of launch/multiproc.distributed_init: join the
    multi-process jax.distributed fleet BEFORE constructing a Session (jax
    must not have created backends yet). Idempotent; see launch/multiproc.py
    for the CLI smoke that proves the fabric."""
    from repro.launch import multiproc
    return multiproc.distributed_init(coordinator, num_processes, process_id)


# ---------------------------------------------------------------------------
# session
# ---------------------------------------------------------------------------

class Session:
    """Runtime facade over one RunSpec. Training state (params, opt_state,
    ef_state, the jitted step) is materialized lazily on first use, so
    lower()/serve()-only sessions never pay for it."""

    def __init__(self, spec: spec_lib.RunSpec):
        self.spec = spec
        self.cfg = self._arch_config(spec)
        self.mesh = self._make_mesh(spec.mesh)
        self.plan = sh.ShardPlan(
            client_granularity=spec.client_granularity,
            state_sharding=spec.state_sharding,
            ef_state_dtype=spec.ef_state_dtype)
        self.step = 0                       # the data cursor: pipeline.batch(step)
        self._stepped = False               # a compile after this is a recompile
        self.history: List[Dict[str, float]] = []
        self._tr: Optional[Dict[str, Any]] = None
        self._last_saved_step: Optional[int] = None
        self._serve_cache: Dict[Any, Any] = {}
        # serve-param placement derives from ONE source of truth: the params
        # version counter, bumped by every mutation path (step_once,
        # restore_from, set_serve_params). serve() re-places exactly when the
        # version moved — there is no second cache key to go stale.
        self._params_version = 0
        self._serve_params: Optional[PyTree] = None   # (version, placed tree)
        self._serve_src: Optional[PyTree] = None      # injected serving tree
        self._publisher = None                        # core/stream.py hook
        self._publish_log = None
        self._bootstrap_every = 0

    # ------------------------------------------------------------- assembly
    @staticmethod
    def _arch_config(spec: spec_lib.RunSpec) -> cb.ArchConfig:
        cfg = cb.get_smoke(spec.arch) if spec.smoke else cb.get(spec.arch)
        if spec.tp_pad_heads:
            cfg = dataclasses.replace(cfg, tp_pad_heads=spec.tp_pad_heads)
        if spec.moe_impl != "dispatch":
            cfg = dataclasses.replace(cfg, moe_impl=spec.moe_impl)
        return cfg

    @staticmethod
    def _make_mesh(name: str):
        if name == "smoke":
            return mesh_lib.make_smoke_mesh()
        if name == "host4":
            return mesh_lib.make_host4_mesh()
        return mesh_lib.make_production_mesh(multi_pod=(name == "multi_pod"))

    def mesh_context(self):
        """``with sess.mesh_context():`` — the spec's mesh as the ambient
        mesh (re-entrant; lower()/serve()/train() enter it themselves)."""
        return jax.set_mesh(self.mesh)

    def _ambient(self):
        # the 1-device smoke path keeps jit's default placement (bit-compat
        # with the pre-Session drivers); real meshes set the ambient mesh
        if self.mesh.size > 1:
            return self.mesh_context()
        return contextlib.nullcontext()

    @property
    def n_clients(self) -> int:
        if self.mesh.size == 1:
            return self.spec.clients
        return sh.n_clients(self.mesh, self.plan)

    @property
    def method(self) -> ef_lib.Method:
        return make_method(self.spec)

    def schedule_table(self) -> Optional[str]:
        """The RESOLVED per-group table for this session's arch — leaf and
        param counts per group, each group's transport plan (with its
        degradation reason, if any) and per-message wire words. None when
        the spec runs the uniform single-compressor path. Costs an
        ``eval_shape`` of init_params, never real allocation."""
        sched = make_schedule(self.spec)
        if sched is None:
            return None
        from repro.core import schedule as sched_lib
        shapes = jax.eval_shape(
            lambda: model_lib.init_params(self.cfg, jax.random.PRNGKey(0)))
        return sched_lib.plan_table(sched, make_method(self.spec), shapes,
                                    eta=self.spec.eta)

    # ------------------------------------------------------- training state
    def _ensure_train(self, template: bool = False) -> Dict[str, Any]:
        """Build the training bundle. With ``template=True`` the state trees
        (params/opt_state/ef_state) are ShapeDtypeStructs from
        ``jax.eval_shape`` — structure and dtypes without paying for
        init_params or the batch-0 gradient evaluation; ``restore_from``
        uses this as the checkpoint template and overwrites every leaf."""
        if self._tr is not None:
            return self._tr
        spec, cfg, mesh, plan = self.spec, self.cfg, self.mesh, self.plan
        n = self.n_clients
        efc = ef_config(spec, mesh, plan)
        opt = opt_lib.make(spec.optimizer, lr=spec.lr)
        pipe = pipe_lib.SyntheticTokens(pipe_lib.DataConfig(
            vocab_size=cfg.vocab_size, seq_len=spec.seq_len,
            global_batch=spec.global_batch, seed=spec.seed, dp_groups=n,
            heterogeneity=spec.heterogeneity))

        def loss_fn(p, b):
            return model_lib.train_loss(cfg, p, b)

        # the step DONATES params, opt_state and ef_state: the new state is
        # written over the old one, which is what lets a full-width run hold
        # one copy of its state in device memory instead of two. A caller
        # that steps the jitted function directly passes the state the
        # previous call returned, never the arguments it gave that call.
        if mesh.size > 1:
            grads_specs = sh._spec_map(
                lambda s: sh.P(sh.client_axis(mesh, plan), *s),
                sh.params_pspecs(cfg, mesh))
            state_specs = sh.ef_state_pspecs(cfg, mesh, plan, efc.method,
                                             downlink=efc.has_downlink,
                                             schedule=efc.schedule,
                                             hops=efc.hops)
            step_fn = jax.jit(dist.make_train_step(
                loss_fn, efc, opt, n, mesh=mesh, grads_specs=grads_specs,
                state_specs=state_specs), donate_argnums=(0, 1, 2))
        else:
            grads_specs = state_specs = None
            step_fn = jax.jit(dist.make_train_step(loss_fn, efc, opt, n),
                              donate_argnums=(0, 1, 2))

        rng = jax.random.PRNGKey(spec.seed)

        # the key is an argument, not a constant of the program: every seed
        # runs the one compiled init
        def init_state(b0, key):
            params = model_lib.init_params(cfg, key)
            # Alg 1 line 2: v⁰ᵢ = g⁰ᵢ = (1/B_init)Σⱼ ∇fᵢ(x⁰, ξ⁰ᵢⱼ)
            _, _, g0 = dist.per_client_value_and_grad(loss_fn, params, b0, n)
            ef_state = dist.init_ef_state(efc, params, n, init_grads=g0)
            return {"params": params, "opt_state": opt.init(params),
                    "ef_state": ef_state}

        # one compiled program: op-by-op, a full-width init spends over a
        # minute compiling and dispatching each primitive on a TPU. Its
        # outputs are distinct buffers even where the init shares one array
        # between leaves (v⁰ = g⁰, h⁰ = g⁰), as the donating step needs: a
        # buffer donated twice in one call is refused
        if mesh.size > 1:
            # the batch is split over the client axes, so each chip computes
            # its own clients' g⁰, and their state is born there
            # (state_specs); params and the optimizer state are replicated,
            # as the step returns them. No chip ever holds every client's
            # state, and the first compile of the step serves every step
            named = lambda s: sh.NamedSharding(mesh, s)  # noqa: E731
            b_specs = sh.batch_pspecs(cfg, mesh, "train", spec.global_batch)
            init_fn = jax.jit(
                init_state,
                in_shardings=({k: named(s) for k, s in b_specs.items()},
                              named(sh.P())),
                out_shardings={
                    "params": named(sh.P()), "opt_state": named(sh.P()),
                    "ef_state": jax.tree_util.tree_map(
                        named, state_specs,
                        is_leaf=lambda x: isinstance(x, sh.P))})
        else:
            init_fn = jax.jit(init_state)
        b0 = pipe_lib.with_prefix_embeds(cfg, pipe.batch(0))
        with self._ambient():
            if template:
                state = jax.eval_shape(init_state, b0, rng)
            else:
                with telemetry.span("init"), telemetry.phase("init"):
                    state = init_fn(b0, rng)
        self._tr = {
            "efc": efc, "opt": opt, "pipe": pipe, "loss_fn": loss_fn,
            "step_fn": step_fn, "rng": rng, "grads_specs": grads_specs,
            "state_specs": state_specs, **state,
        }
        return self._tr

    @property
    def params(self) -> PyTree:
        return self._ensure_train()["params"]

    @property
    def opt_state(self) -> PyTree:
        return self._ensure_train()["opt_state"]

    @property
    def ef_state(self) -> PyTree:
        return self._ensure_train()["ef_state"]

    @property
    def step_fn(self):
        """The jitted production train step
        ``(params, opt_state, ef_state, batch, rng, step) → (…, metrics)`` —
        benchmarks time this directly against a fixed batch. It donates its
        three state arguments: feed each call the state the last one
        returned."""
        return self._ensure_train()["step_fn"]

    @property
    def efc(self) -> dist.EFConfig:
        """The EFConfig the step runs its EF round with."""
        return self._ensure_train()["efc"]

    @property
    def shard_specs(self):
        """``(grads_specs, state_specs)``: how the multi-device step lays
        out the per-client gradients and the EF state; ``(None, None)`` on
        one device."""
        tr = self._ensure_train()
        return tr["grads_specs"], tr["state_specs"]

    def batch_for(self, step: int) -> PyTree:
        """The (frontend-padded) global batch the pipeline yields for
        ``step`` — deterministic in (seed, step), restart-safe."""
        tr = self._ensure_train()
        return pipe_lib.with_prefix_embeds(self.cfg, tr["pipe"].batch(step))

    # -------------------------------------------------------------- training
    def step_once(self) -> Dict[str, jax.Array]:
        """Advance exactly one training step; returns the step metrics.
        The unit benchmarks time (benchmarks/kernel_bench.py). Runs under
        the host spans ``repro.step`` > ``repro.batch``, ``repro.dispatch``,
        ``repro.publish``, and counts its compiles in phase ``step``."""
        tr = self._ensure_train()
        with telemetry.step_span(self.step):
            # the publisher re-encodes this round's wire from the PRE-step
            # h, which the donating step overwrites: keep a copy of it on
            # the device
            h = None if self._publisher is None else tr["ef_state"].get("h")
            h_prev = None if h is None else jax.tree_util.tree_map(
                jnp.copy, h)
            with self._ambient():
                with telemetry.span("batch"):
                    batch = self.batch_for(self.step)
                with telemetry.span("dispatch"), telemetry.phase(
                        "step", step=self.step, warm=self._stepped):
                    (tr["params"], tr["opt_state"], tr["ef_state"],
                     m) = tr["step_fn"](
                        tr["params"], tr["opt_state"], tr["ef_state"], batch,
                        jax.random.fold_in(tr["rng"], self.step), self.step)
            self._stepped = True
            self.step += 1
            self._params_version += 1
            if self._publisher is not None:
                # publish this round's downlink wire (verified bit-exact
                # against the step's own h before anything hits the log)
                with telemetry.span("publish"):
                    self._publisher.publish(
                        self.step, tr["ef_state"]["server"], h_prev,
                        tr["ef_state"].get("h"))
                    if self._bootstrap_every \
                            and self.step % self._bootstrap_every == 0:
                        self._write_bootstrap()
        return m

    def train(self, steps: int, log_every: int = 10, verbose: bool = False
              ) -> List[Dict[str, float]]:
        """Train until the global step counter reaches ``steps`` (absolute —
        a resumed session continues where the checkpoint left off). Appends
        to ``self.history`` and returns the new entries. Saves a full-state
        checkpoint every ``spec.ckpt_every`` steps and at the end whenever
        ``spec.ckpt_dir`` is set."""
        spec = self.spec
        self._ensure_train()
        new: List[Dict[str, float]] = []
        t0, start = time.time(), self.step
        seen = len(telemetry.recompiles())
        while self.step < steps:
            m = self.step_once()
            step = self.step - 1
            if verbose:
                # a compile after the first step is a recompile: a new
                # shape or dtype reached the step
                recompiled = telemetry.recompiles()
                for r in recompiled[seen:]:
                    print(f"recompiled {r['fun']} at step {r['step']} "
                          f"({r['seconds']:.2f}s)", flush=True)
                seen = len(recompiled)
            if (log_every and step % log_every == 0) or step == steps - 1:
                rec = {"step": step, "loss": float(m["loss"]),
                       "g_norm": float(m["g_norm"])}
                self.history.append(rec)
                new.append(rec)
                if verbose:
                    print(f"step {step:5d} loss {rec['loss']:8.4f} "
                          f"g_norm {rec['g_norm']:.3e} "
                          f"({(time.time()-t0)/max(step-start+1,1):.2f}s/step)",
                          flush=True)
            if (spec.ckpt_dir and spec.ckpt_every
                    and self.step % spec.ckpt_every == 0):
                self.save()
        # end-of-train save, unless the periodic save just wrote this step
        if spec.ckpt_dir and self._last_saved_step != self.step:
            self.save()
        return new

    def evaluate(self, batches: int = 2) -> float:
        """Mean loss over ``batches`` held-out batches (the synthetic stream
        at seed+1 — disjoint from every training batch) at current params."""
        tr = self._ensure_train()
        cfg, spec = self.cfg, self.spec
        eval_pipe = pipe_lib.SyntheticTokens(pipe_lib.DataConfig(
            vocab_size=cfg.vocab_size, seq_len=spec.seq_len,
            global_batch=spec.global_batch, seed=spec.seed + 1,
            dp_groups=self.n_clients, heterogeneity=spec.heterogeneity))
        if "eval_fn" not in tr:             # jit once, not per evaluate() call
            tr["eval_fn"] = jax.jit(lambda p, b: tr["loss_fn"](p, b)[0])
        loss_j = tr["eval_fn"]
        with self._ambient():
            losses = [float(loss_j(
                tr["params"], pipe_lib.with_prefix_embeds(
                    cfg, eval_pipe.batch(i)))) for i in range(batches)]
        return sum(losses) / max(len(losses), 1)

    # --------------------------------------------------------------- serving
    def serve_source(self) -> PyTree:
        """THE parameter tree serve() places — in priority order: the
        injected serving tree (the wire-subscriber path, launch/fleet.py),
        else the live training tree, else a fresh init. Every path that
        mutates the answer bumps ``_params_version``, which is the only
        cache key serve() consults."""
        if self._serve_src is not None:
            return self._serve_src
        if self._tr is not None:
            return self._tr["params"]
        return model_lib.init_params(self.cfg, jax.random.PRNGKey(
            self.spec.seed))

    def set_serve_params(self, params: PyTree) -> None:
        """Inject the tree serve() must use from now on (wire subscribers
        push their post-apply params here between request batches)."""
        self._serve_src = params
        self._params_version += 1

    def serve(self, tokens=None, batch: int = 4, prompt_len: int = 128,
              decode_steps: int = 32, prompt_lens=None,
              decode_hook=None) -> Dict[str, Any]:
        """Batched prefill + greedy decode THROUGH launch/build.py on the
        session mesh: inputs/params/cache are placed onto the
        ``build_prefill``/``build_decode`` shardings (trivial on the 1-device
        smoke mesh, real placement on pod meshes) instead of jitting
        unsharded lambdas. Returns token ids + timings.

        ``prompt_lens`` (per-row true lengths, ≤ S) makes prefill read each
        row's logits at its LAST REAL token instead of the padded tail, so
        right-padding never contaminates the first generated token (padding
        with id 0 is indistinguishable from a real vocab-0 token otherwise).
        ``decode_hook(i)`` is called between decode steps — the continuous
        wire-sync point (launch/fleet.py): if the hook moves the params
        version (set_serve_params), the remaining steps decode with the
        fresh tree."""
        cfg, mesh, spec = self.cfg, self.mesh, self.spec
        rng = jax.random.PRNGKey(spec.seed)
        if tokens is None:
            tokens = jax.random.randint(rng, (batch, prompt_len), 0,
                                        cfg.vocab_size)
        B, S = tokens.shape
        # serving uses the PRODUCTION padding (PREFIX_PAD_SPEC) so the
        # arrays run at exactly the shapes build_prefill/build_decode
        # lowered and dryrun validated — the deduped padding rule must not
        # diverge between the specs and the arrays inside one call
        pad = pipe_lib.PREFIX_PAD_SPEC
        n_prefix = pipe_lib.prefix_token_count(cfg, pad_to=pad)

        # the jitted pair + sharding specs are cached per serving geometry:
        # a Session used as a serving loop must not recompile per request
        key = (B, S, decode_steps)
        if key not in self._serve_cache:
            shape = cb.InputShape("serve", S, B, "prefill")
            fn_pre, (p_spec, b_spec, c_spec) = build_lib.build_prefill(
                cfg, shape, mesh, decode_budget=decode_steps)
            fn_dec, (_, _, t_spec, _) = build_lib.build_decode(
                cfg, dataclasses.replace(shape, kind="decode"), mesh,
                decode_budget=decode_steps)
            self._serve_cache[key] = (jax.jit(fn_pre), jax.jit(fn_dec),
                                      p_spec, b_spec, c_spec, t_spec)
        prefill, decode, p_spec, b_spec, c_spec, t_spec = \
            self._serve_cache[key]
        shard_of = lambda tree: jax.tree_util.tree_map(
            lambda s: s.sharding, tree)

        with jax.set_mesh(mesh):
            # placed params are cached on the params VERSION — the single
            # source of truth every mutation path bumps (step_once,
            # restore_from, set_serve_params) — so a serving loop never
            # re-places an unchanged tree and never serves a stale one
            # (restoring a checkpoint at the same step counter used to slip
            # past the old step-keyed cache)
            if self._serve_params is None \
                    or self._serve_params[0] != self._params_version:
                self._serve_params = (
                    self._params_version,
                    jax.device_put(self.serve_source(), shard_of(p_spec)))
            params = self._serve_params[1]
            raw = pipe_lib.with_prefix_embeds(cfg, {"tokens": tokens},
                                              pad_to=pad)
            batch_in = dict(jax.device_put(raw, shard_of(b_spec)))
            if prompt_lens is not None:
                # not part of b_spec (the lowered sharding tree) — a small
                # replicated int32 vector placed with default sharding; the
                # jitted prefill retraces once for the extra pytree key
                batch_in["prompt_lens"] = jax.device_put(
                    jnp.asarray(prompt_lens, jnp.int32))
            cache = jax.device_put(
                model_lib.init_cache(cfg, B, n_prefix + S + decode_steps),
                shard_of(c_spec))

            t0 = time.time()
            logits, cache = prefill(params, batch_in, cache)
            logits.block_until_ready()
            t_prefill = time.time() - t0

            tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
            tok = jax.device_put(tok, t_spec.sharding)
            out_tokens = [tok]
            t0 = time.time()
            for i in range(decode_steps):
                if decode_hook is not None:
                    # continuous sync: the hook may apply fresh wire records
                    # (bumping the params version) between decode steps
                    decode_hook(i)
                    if self._serve_params[0] != self._params_version:
                        self._serve_params = (
                            self._params_version,
                            jax.device_put(self.serve_source(),
                                           shard_of(p_spec)))
                        params = self._serve_params[1]
                pos = jnp.asarray(n_prefix + S + i, jnp.int32)
                logits, cache = decode(params, cache, tok, pos)
                tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
                out_tokens.append(tok)
            jax.block_until_ready(tok)
            t_decode = time.time() - t0

        gen = jnp.concatenate(out_tokens, axis=1)
        cache_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(cache))
        return {
            "tokens": jax.device_get(gen),
            "prefill_s": t_prefill, "decode_s": t_decode,
            "prefill_tok_s": B * S / max(t_prefill, 1e-9),
            "decode_tok_s": decode_steps * B / max(t_decode, 1e-9),
            "cache_bytes": cache_bytes,
        }

    # --------------------------------------------------------------- dry-run
    def lower(self, shape_name: Optional[str] = None):
        """The dry-run artifact: ``jax.jit(step).lower(*input_specs)`` for the
        named InputShape (default: ``spec.shape``; None → the spec's custom
        train geometry) on the session mesh. ``.compile()`` the result under
        ``self.mesh_context()`` for memory/HLO analysis (launch/dryrun.py)."""
        name = shape_name if shape_name is not None else self.spec.shape
        if name is not None:
            shape = cb.INPUT_SHAPES[name]
        else:
            shape = cb.InputShape("train_custom", self.spec.seq_len,
                                  self.spec.global_batch, "train")
        with self.mesh_context():
            if shape.kind == "train":
                efc = ef_config(self.spec, self.mesh, self.plan)
                fn, specs = build_lib.build_step(
                    self.cfg, shape, self.mesh, self.plan, efc,
                    optimizer_name=self.spec.optimizer, lr=self.spec.lr)
            else:
                fn, specs = build_lib.build_step(
                    self.cfg, shape, self.mesh, self.plan)
            return jax.jit(fn).lower(*specs)

    # ---------------------------------------------------------- checkpointing
    def save(self, path: Optional[str] = None) -> str:
        """Write the FULL training state — params, opt_state, ef_state, the
        data cursor, and the spec itself — so resume needs nothing else.
        The ``step`` in meta IS the data cursor: the pipeline is
        stateless-addressable (``pipe.batch(step)``), so restoring step
        resumes the exact data stream."""
        tr = self._ensure_train()
        if path is None:
            assert self.spec.ckpt_dir, "no ckpt_dir in spec and no path given"
            path = os.path.join(self.spec.ckpt_dir,
                                f"step_{self.step:08d}.npz")
        state = {"params": tr["params"], "opt_state": tr["opt_state"],
                 "ef_state": tr["ef_state"]}
        ckpt_lib.save(path, state, step=self.step, spec=self.spec)
        self._last_saved_step = self.step
        return path

    def restore_from(self, path: str, allow_spec_mismatch: bool = False
                     ) -> None:
        """Restore full state from ``path`` into this session. Refuses a
        checkpoint written by a different RunSpec (hash recorded by save)
        unless ``allow_spec_mismatch``."""
        meta = ckpt_lib.read_meta(path)
        stored = meta.get("spec_hash")
        if stored is not None and stored != self.spec.spec_hash() \
                and not allow_spec_mismatch:
            diff = ""
            if "spec" in meta:
                other = spec_lib.RunSpec.from_dict(meta["spec"])
                diff = "\n  - " + "\n  - ".join(self.spec.diff(other))
            raise ValueError(
                f"checkpoint {path} was written by a different RunSpec "
                f"(hash {stored} != {self.spec.spec_hash()}); refusing to "
                f"resume across experiment definitions.{diff}\n"
                "Pass allow_spec_mismatch=True / --allow-spec-mismatch to "
                "override.")
        # template=True: the like-tree only needs structure/shapes/dtypes —
        # never pay init_params + a full batch-0 gradient pass just to
        # overwrite every leaf from the checkpoint
        created = self._tr is None
        tr = self._ensure_train(template=True)
        like = {"params": tr["params"], "opt_state": tr["opt_state"],
                "ef_state": tr["ef_state"]}
        try:
            state, meta = ckpt_lib.restore(path, like)
        except BaseException:
            if created:
                # never leave abstract template leaves behind a failed
                # restore — the session must stay usable (fresh init)
                self._tr = None
            raise
        tr["params"] = state["params"]
        tr["opt_state"] = state["opt_state"]
        tr["ef_state"] = state["ef_state"]
        self.step = int(meta["step"])
        # restored params are a new serving truth even when the step counter
        # did not move — the version counter is what serve() keys on, and an
        # injected serve tree (set_serve_params) is superseded by the restore
        self._serve_src = None
        self._params_version += 1

    # -------------------------------------------------------- wire streaming
    def publish_to(self, stream_dir: str, bootstrap_every: int = 0):
        """Attach a core/stream.py Publisher: every subsequent step_once
        appends this round's downlink wire records to ``stream_dir`` (one
        per transport leg, verified bit-exact against the step's own h).
        Writes a full-state bootstrap checkpoint into the stream whenever
        the log has no records at or past the current step, so a replica can
        join from the stream directory alone (checkpoint + replay);
        ``bootstrap_every`` adds periodic re-bootstraps for cheaper
        mid-stream joins and gap resyncs. Returns the WireLog."""
        from repro.core import stream as stream_lib
        tr = self._ensure_train()
        efc = tr["efc"]
        legs = stream_lib.resolve_legs(
            tr["params"], schedule=efc.schedule,
            down_carrier=efc.down_carrier,
            down_compressor=efc.down_compressor)
        log = stream_lib.WireLog(stream_dir)
        self._publish_log = log
        self._bootstrap_every = int(bootstrap_every)
        last = log.last_step()
        if last is None or last < self.step:
            # nothing in the log can reach this trainer's state by replay:
            # anchor the stream here so subscribers have a join point
            self._write_bootstrap()
        self._publisher = stream_lib.Publisher(
            log, self.spec.spec_hash(), legs, tr["rng"])
        return log

    def _write_bootstrap(self) -> str:
        """One full-state checkpoint INSIDE the stream directory — what
        replicas join from and resync to (spec embedded, foreign-spec
        refusal included, exactly like ckpt_dir checkpoints)."""
        tr = self._ensure_train()
        path = self._publish_log.bootstrap_path(self.step)
        if not os.path.exists(path):
            state = {"params": tr["params"], "opt_state": tr["opt_state"],
                     "ef_state": tr["ef_state"]}
            ckpt_lib.save(path, state, step=self.step, spec=self.spec)
        return path

    @classmethod
    def resume(cls, ckpt_dir: str, spec: Optional[spec_lib.RunSpec] = None,
               overrides: Optional[Dict[str, Any]] = None,
               allow_spec_mismatch: bool = False) -> "Session":
        """Reconstruct a run from its latest checkpoint WITHOUT re-passing
        flags: the RunSpec embedded in checkpoint meta is the source of
        truth. ``overrides`` layers individual field changes ON TOP of the
        embedded spec (the driver maps explicitly passed flags here, so
        '--resume --eta 0.2' means 'the same run, new eta' — never 'defaults
        plus eta'); experiment-defining overrides still require
        ``allow_spec_mismatch``. Pass ``spec`` to insist on an exact spec
        instead — it must hash-match the checkpoint unless overridden."""
        path = ckpt_lib.latest(ckpt_dir)
        if path is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir!r}")
        meta = ckpt_lib.read_meta(path)
        if spec is None:
            if "spec" not in meta:
                raise ValueError(
                    f"checkpoint {path} has no embedded RunSpec (pre-Session "
                    "format); pass spec= explicitly")
            embedded = spec_lib.RunSpec.from_dict(meta["spec"])
            spec = dataclasses.replace(embedded, ckpt_dir=ckpt_dir,
                                       **(overrides or {}))
            if spec.spec_hash() == embedded.spec_hash():
                allow_spec_mismatch = True  # no experiment-defining change
        elif overrides:
            raise ValueError("pass either spec= or overrides=, not both")
        sess = cls(spec)
        sess.restore_from(path, allow_spec_mismatch=allow_spec_mismatch)
        return sess
