"""Chip smoke: the EF21-SGDM trainer, and the replica that serves from its
downlink wire, run on a TPU at smollm-360m's published widths (32 layers,
d_model 960, vocab 49152; weights random from ``--seed``).

    python chip_smoke.py                # one chip: device, train, serve
    python chip_smoke.py --four-chips   # four chips: the sharded EF round
                                        # against the vmap round, only

Everything runs in this one process through the user-facing entry points
(``RunSpec`` → ``Session``, ``Fleet``); data is the repo's own seeded
``SyntheticTokens``. Phases:

* device — refuses to run anywhere but a TPU: the Pallas kernels compile
  for Mosaic there, and a CPU run would say nothing about the chip.
* train — 3 EF21-SGDM/Block-TopK steps on each of the ``dense``, ``fused``,
  ``fused_quant8`` and ``fused_quant4`` carriers (2 clients, global batch
  8, sequence 1024). Losses must be finite, every kernel carrier's compiled
  step must hold a Mosaic kernel (``tpu_custom_call``), and ``fused`` must
  land on ``dense``'s server estimate within the carrier tests' tolerance.
* serve — a trainer with a ``quant8`` downlink takes 2 steps, then 2 more
  publishing to a stream inside the checkout (one bootstrap, then the
  wire), timed against the 2 that did not publish; the trainer is freed, a
  one-replica ``Fleet`` joins from the bootstrap, replays the wire, answers
  4 requests (prompt 128, 16 new tokens), and its params must equal the
  trainer's bit for bit. ``ProcessFleet`` must refuse to start a worker on
  the chip this process holds.
* four chips — the shard_map EF round with one client on each of four
  chips (mesh ``host4``: data=4), against the vmap round of the same four
  clients on one chip, on ``dense`` and ``fused_quant8``: each of 3 steps
  feeds both rounds the same state and per-client gradients, and their
  server estimates must agree within the carrier tests' tolerance.

Times, losses and memory printed here are one run's readings. Any failure
raises, so the exit code is non-zero and the final line is never printed;
the final line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the one-chip training geometry: 2 clients' f32 EF state plus params is
# 8.2 GiB, and the donated step's temporaries bring the dense..fused
# carriers to 14.4..15.5 GiB of the v5e's 15.75 GiB
TRAIN = dict(arch="smollm-360m", smoke=False, method="ef21_sgdm",
             compressor="block_topk", clients=2, global_batch=8,
             seq_len=1024)
CARRIERS = ("dense", "fused", "fused_quant8", "fused_quant4")
KERNEL_CARRIERS = ("fused", "fused_quant8", "fused_quant4")
STEPS = 3
RTOL, ATOL = 1e-4, 1e-5               # tests/test_carriers.py
# serving: with f32 EF state the quant8-downlink trainer's step needs
# 16.69 GiB (its broadcast memory h is one more params-sized tree), so the
# serve trainer keeps its client state in bfloat16
SERVE = dict(TRAIN, carrier="dense", downlink_carrier="quant8",
             ef_state_dtype="bfloat16")
SERVE_STEPS = 2
REQUESTS, PROMPT, NEW_TOKENS = 4, 128, 16
# four chips: mesh 'host4' is (data=4, model=1) on a 2x2 host, one EF client
# per chip in ef_round_sharded: the data-parallel layout users run, with
# four sequences per client.
FOUR = dict(TRAIN, global_batch=16, mesh="host4")
FOUR_CARRIERS = ("dense", "fused_quant8")
# the vmap round's reference runs on one chip piece by piece, since four
# clients' state and gradients do not fit one chip whole: a piece is at
# most this many elements of one leaf, cut into whole Block-TopK blocks
PIECE = 1 << 24


def log(msg: str) -> None:
    print(msg, flush=True)


def _gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


def device_phase(n_chips: int):
    """The devices JAX found; exits non-zero unless they are ``n_chips``
    TPUs."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={importlib.metadata.version('jaxlib')} "
        f"libtpu={_libtpu_version()}")
    if d0.platform != "tpu":
        sys.exit(f"[device] FAIL: JAX found no TPU (platform "
                 f"{d0.platform!r}); this smoke runs on the chip only")
    if len(devs) < n_chips:
        sys.exit(f"[device] FAIL: {n_chips} TPU chips needed, "
                 f"{len(devs)} found")
    from repro.launch import fleet as fleet_lib
    nodes = fleet_lib.accelerator_nodes()
    log(f"[device] accelerator nodes {nodes}, held by this process "
        f"{fleet_lib.held_nodes(nodes)}")
    return d0, len(devs)


def _libtpu_version() -> str:
    try:
        return importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def _stats(dev) -> dict:
    return dev.memory_stats()


def _peak(dev) -> int:
    return int(_stats(dev)["peak_bytes_in_use"])


def _free() -> None:
    """Drop the last session's arrays and compiled programs before the
    next one allocates: one full-width session fills the chip."""
    import jax
    gc.collect()
    jax.clear_caches()


def run_session(spec, label: str, steps: int = STEPS):
    """Build ``spec``'s session, compile its step and take ``steps`` steps;
    losses must be finite, and a kernel carrier's compiled step must hold
    its Mosaic kernel. Returns the session."""
    import jax
    import numpy as np
    from repro.launch.session import Session

    sess = Session(spec)
    t0 = time.perf_counter()
    state = (sess.params, sess.opt_state, sess.ef_state)
    jax.block_until_ready(state)
    t_init = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(sess.params))
    cfg = sess.cfg
    log(f"[{label}] {cfg.name}: layers={cfg.num_layers} "
        f"d_model={cfg.d_model} vocab={cfg.vocab_size} "
        f"params={n_params / 1e6:.1f}M clients={sess.n_clients} "
        f"mesh={dict(sess.mesh.shape)} init {t_init:.1f}s")
    # lowered in the context step_once calls it in (the mesh is ambient
    # only on multi-device meshes), so the steps reuse this compile
    ambient = sess.mesh_context() if sess.mesh.size > 1 \
        else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ambient:
        compiled = sess.step_fn.lower(
            *state, sess.batch_for(sess.step), jax.random.PRNGKey(0),
            sess.step).compile()
    t_compile = time.perf_counter() - t0
    hlo = compiled.as_text()
    del state, compiled
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        m = sess.step_once()
        jax.block_until_ready(m)
        times.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    log(f"[{label}] compile {t_compile:.1f}s, steps "
        + " ".join(f"{t:.3f}s" for t in times)
        + ", losses " + " ".join(f"{x:.4f}" for x in losses)
        + f", tpu_custom_call={'tpu_custom_call' in hlo}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"[{label}] non-finite loss: {losses}")
    if spec.carrier in KERNEL_CARRIERS and "tpu_custom_call" not in hlo:
        raise AssertionError(f"[{label}] the compiled step holds no Mosaic "
                             "kernel")
    return sess


def _server(sess):
    import jax
    import numpy as np
    return [np.asarray(x) for x in
            jax.tree_util.tree_leaves(jax.device_get(sess.ef_state["server"]))]


def _compare(label: str, got, ref) -> bool:
    """Whether every server-estimate element of ``got`` is within
    ``np.testing.assert_allclose``'s tolerance of ``ref``; prints the
    largest difference and how many elements are outside."""
    import numpy as np
    worst = max(float(np.max(np.abs(a - b))) for a, b in zip(got, ref))
    bad = sum(int(np.count_nonzero(~np.isclose(a, b, rtol=RTOL, atol=ATOL)))
              for a, b in zip(got, ref))
    total = sum(b.size for b in ref)
    log(f"[{label}] server estimate max |diff| = {worst:.3e}, {bad} of "
        f"{total} elements outside rtol={RTOL}, atol={ATOL}")
    return bad == 0


def train_phase(seed: int, dev) -> None:
    from repro.launch.spec import RunSpec

    servers = {}
    for carrier in CARRIERS:
        spec = RunSpec(**TRAIN, carrier=carrier, seed=seed)
        sess = run_session(spec, f"train {carrier}")
        if carrier in ("dense", "fused"):
            servers[carrier] = _server(sess)
        stats = _stats(dev)
        log(f"[train {carrier}] peak_bytes_in_use "
            f"{_gib(stats['peak_bytes_in_use'])} of "
            f"{_gib(stats['bytes_limit'])}")
        del sess
        _free()
    if not _compare("train fused vs dense", servers["fused"],
                    servers["dense"]):
        raise AssertionError("[train] fused left dense's server estimate")


def serve_phase(seed: int, dev) -> None:
    import jax
    import numpy as np
    from repro.launch import fleet as fleet_lib
    from repro.launch.spec import RunSpec

    stream = os.path.join(ROOT, ".smoke_stream")   # git-ignored
    shutil.rmtree(stream, ignore_errors=True)
    try:
        spec = RunSpec(**SERVE, seed=seed)
        from repro.launch.session import Session
        trainer = Session(spec)
        # this process now holds the chip: a replica worker process could
        # not reach it, and ProcessFleet must say so before spawning one
        why_not = fleet_lib.can_spawn_workers(1)
        log(f"[serve] replica worker processes: {why_not or 'would start'}")
        if why_not is None:
            raise AssertionError("[serve] ProcessFleet would start a worker "
                                 "on the chip this process holds")
        times, losses = [], []

        def timed_steps(k):
            for _ in range(k):
                t0 = time.perf_counter()
                m = trainer.step_once()
                jax.block_until_ready((m, trainer.ef_state))
                times.append(time.perf_counter() - t0)
                losses.append(float(m["loss"]))

        timed_steps(SERVE_STEPS)                    # not publishing
        t0 = time.perf_counter()
        trainer.publish_to(stream)                  # the one bootstrap
        boot = sum(os.path.getsize(os.path.join(dp, f))
                   for dp, _, fs in os.walk(stream) for f in fs)
        log(f"[serve] bootstrap at step {trainer.step}: {boot / 1e9:.2f} GB "
            f"written in {time.perf_counter() - t0:.1f}s")
        timed_steps(SERVE_STEPS)                    # publishing each round
        log(f"[serve] trainer steps "
            + " ".join(f"{t:.3f}s" for t in times)
            + f" (the first compiles; the last {SERVE_STEPS} publish), "
            "losses " + " ".join(f"{x:.4f}" for x in losses)
            + f"; a publishing step {times[-1]:.3f}s against "
            f"{times[SERVE_STEPS - 1]:.3f}s not publishing")
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"[serve] non-finite trainer loss: {losses}")
        head_step = trainer.step
        head = [np.asarray(x) for x in
                jax.tree_util.tree_leaves(jax.device_get(trainer.params))]
        del trainer
        _free()

        t0 = time.perf_counter()
        fleet = fleet_lib.Fleet(stream, n_replicas=1, lags=(0,),
                                decode_budget=REQUESTS * NEW_TOKENS,
                                max_batch=REQUESTS, prompt_len=PROMPT)
        rep = fleet.replicas[0]
        log(f"[serve] replica joined at step {rep.step} in "
            f"{time.perf_counter() - t0:.1f}s")
        reqs = fleet_lib.synthetic_requests(
            REQUESTS, rate=0.0, prompt_len=PROMPT,
            max_new_tokens=NEW_TOKENS, vocab_size=rep.session.cfg.vocab_size,
            seed=seed)
        out = fleet.run(reqs)
        done = out["requests"]
        log(f"[serve] {len(done)} requests in {out['batches']} batches "
            f"(compile included): p50 {out['p50_ms']:.0f} ms, "
            f"p99 {out['p99_ms']:.0f} ms; replica at step {rep.step}")
        vocab = rep.session.cfg.vocab_size
        if len(done) != REQUESTS or any(
                r.tokens_generated != NEW_TOKENS
                or not np.all((r.tokens_out >= 0) & (r.tokens_out < vocab))
                for r in done):
            raise AssertionError("[serve] a request came back short or "
                                 "with out-of-vocabulary tokens")
        if rep.step != head_step:
            raise AssertionError(f"[serve] replica at step {rep.step}, "
                                 f"trainer head at {head_step}")
        got = [np.asarray(x) for x in
               jax.tree_util.tree_leaves(jax.device_get(rep.params))]
        same = len(got) == len(head) and all(
            a.dtype == b.dtype and np.array_equal(a, b)
            for a, b in zip(got, head))
        log(f"[serve] replica params bit-identical to trainer head: {same}")
        if not same:
            raise AssertionError("[serve] replica params differ from the "
                                 "trainer's")
        log(f"[serve] peak_bytes_in_use {_gib(_peak(dev))}")
    finally:
        shutil.rmtree(stream, ignore_errors=True)


def _pieces(shape, comp):
    """``(start, rows)`` ranges along axis 0 that cut a leaf of ``shape``
    into pieces of at most PIECE elements, each whole blocks of the leaf's
    own Block-TopK geometry, so the round selects, quantizes and averages
    the same blocks in a piece as in the leaf; the whole leaf where no such
    cut exists."""
    import math
    d = math.prod(shape)
    whole = [(0, shape[0] if shape else 1)]
    if d <= PIECE or len(shape) < 2:
        return whole
    row = d // shape[0]
    geom = comp.geom(d)[1:]
    for rows in range(shape[0] - 1, 0, -1):
        n = rows * row
        if (shape[0] % rows == 0 and n <= PIECE and n % geom[0] == 0
                and comp.geom(n)[1:] == geom):
            return [(start, rows) for start in range(0, shape[0], rows)]
    return whole


def _on(dev, x):
    """``x``'s copy on ``dev``: its replica there, or the gathered array."""
    import jax
    for shard in x.addressable_shards:
        if shard.device == dev and shard.data.shape == x.shape:
            return shard.data
    return jax.device_put(x, dev)


def four_chip_phase(seed: int) -> None:
    """Each carrier's EF round on the four chips (``ef_round_sharded``,
    one client per chip) against the vmap round (``ef_round``) on one chip.
    Per step both rounds start from the same EF state and are fed the same
    per-client gradients, computed once on the four chips, and their server
    estimates are compared; then the sharded round's state and the
    optimizer step carry on, as the train step does. The gradients are
    shared because the bf16 model's matmuls round differently in a
    one-client program than in a batched one; the rounds are what the four
    chips add. Both carriers run before a mismatch fails the phase."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed as dist
    from repro.launch import shardings as sh
    from repro.launch.session import Session
    from repro.launch.spec import RunSpec
    from repro.models import model as model_lib
    from repro.optim import optimizer as opt_lib

    dev0 = jax.devices()[0]
    is_p = lambda x: isinstance(x, P)                       # noqa: E731

    @jax.jit
    def close(a, b):                 # np.isclose's rule; NaN is never close
        diff = jnp.abs(a - b)
        return jnp.max(diff), jnp.sum(~(diff <= ATOL + RTOL * jnp.abs(b)))

    rows_of = jax.jit(jax.lax.dynamic_slice_in_dim,
                      static_argnames=("slice_size", "axis"))

    failed = []
    for carrier in FOUR_CARRIERS:
        label = f"4-chip {carrier}"
        spec = RunSpec(**FOUR, carrier=carrier, seed=seed)
        sess = Session(spec)
        t0 = time.perf_counter()
        params, opt_state, ef_state = sess.params, sess.opt_state, \
            sess.ef_state
        jax.block_until_ready(ef_state)
        cfg, mesh, n, efc = sess.cfg, sess.mesh, sess.n_clients, sess.efc
        log(f"[{label}] {cfg.name}: layers={cfg.num_layers} "
            f"d_model={cfg.d_model} vocab={cfg.vocab_size} clients={n} "
            f"mesh={dict(mesh.shape)} init "
            f"{time.perf_counter() - t0:.1f}s")
        grads_specs, state_specs = sess.shard_specs
        named = lambda s: NamedSharding(mesh, s)            # noqa: E731
        b_shard = {k: named(s) for k, s in sh.batch_pspecs(
            cfg, mesh, "train", spec.global_batch).items()}
        grad_fn = jax.jit(
            lambda p, b: dist.per_client_value_and_grad(
                lambda q, c: model_lib.train_loss(cfg, q, c), p, b, n)[::2],
            out_shardings=(named(P()), jax.tree_util.tree_map(
                named, grads_specs, is_leaf=is_p)))
        round_fn = jax.jit(
            lambda g, st, r, t: dist.ef_round_sharded(
                efc, g, st, r, mesh, grads_specs, state_specs, step=t)[1],
            donate_argnums=(0, 1))
        ref_fn = jax.jit(lambda g, st, r, t: dist.ef_round(
            efc, g, st, r, step=t)[1]["server"])
        opt = opt_lib.make(spec.optimizer, lr=spec.lr)

        def update(p, o, g, t):
            upd, o = opt.update(g, o, p, t)
            return opt_lib.apply_updates(p, upd), o
        update_fn = jax.jit(update, donate_argnums=(0, 1))

        comp = efc.method.compressor
        shapes = [x.shape for x in jax.tree_util.tree_leaves(params)]
        cuts = [_pieces(shp, comp) for shp in shapes]
        log(f"[{label}] vmap reference on {dev0}: {sum(map(len, cuts))} "
            f"pieces of {len(shapes)} leaves, at most {PIECE} elements each")
        rng0 = jax.random.PRNGKey(spec.seed)
        compiled = None
        for t in range(STEPS):
            r = jax.random.fold_in(jax.random.fold_in(rng0, t), 1)
            t0 = time.perf_counter()
            with sess.mesh_context():
                batch = jax.device_put(sess.batch_for(t), b_shard)
                loss, grads = grad_fn(params, batch)
                jax.block_until_ready(grads)
            t_grad = time.perf_counter() - t0

            # the vmap round on one chip, from the same state and gradients
            t0 = time.perf_counter()
            g_l = jax.tree_util.tree_leaves(grads)
            c_l = {k: jax.tree_util.tree_leaves(v)
                   for k, v in ef_state["clients"].items()}
            s_l = [_on(dev0, x) for x in
                   jax.tree_util.tree_leaves(ef_state["server"])]
            ref = []
            for j, cut in enumerate(cuts):
                parts = []
                for start, rows in cut:
                    whole = rows == (shapes[j][0] if shapes[j] else 1)
                    take = (lambda x, ax: x) if whole else (
                        lambda x, ax: rows_of(x, start, slice_size=rows,
                                              axis=ax))
                    g = jax.device_put(take(g_l[j], 1), dev0)
                    st = {"clients": {k: [jax.device_put(take(v[j], 1), dev0)]
                                      for k, v in c_l.items()},
                          "server": [take(s_l[j], 0)]}
                    parts.append(ref_fn([g], st, r, t)[0])
                ref.append(parts[0] if len(parts) == 1
                           else jnp.concatenate(parts, axis=0))
            jax.block_until_ready(ref)
            t_ref = time.perf_counter() - t0

            t0 = time.perf_counter()
            with sess.mesh_context():
                if compiled is None:
                    compiled = round_fn.lower(grads, ef_state, r, t).compile()
                    if carrier in KERNEL_CARRIERS \
                            and "tpu_custom_call" not in compiled.as_text():
                        raise AssertionError(f"[{label}] the sharded round "
                                             "holds no Mosaic kernel")
                del g_l, c_l, s_l          # views of the buffers donated next
                ef_state = compiled(grads, ef_state, r, t)
                del grads
                params, opt_state = update_fn(params, opt_state,
                                              ef_state["server"], t)
                jax.block_until_ready(params)
            t_round = time.perf_counter() - t0

            worst, bad, total = 0.0, 0, 0
            for x, y in zip(jax.tree_util.tree_leaves(ef_state["server"]),
                            ref):
                m, b = close(_on(dev0, x), y)
                worst, bad, total = max(worst, float(m)), bad + int(b), \
                    total + y.size
            log(f"[{label}] step {t + 1}: loss {float(loss):.4f}; grads "
                f"{t_grad:.3f}s, vmap reference {t_ref:.3f}s, sharded round "
                f"+ update {t_round:.3f}s"
                + (" (compiles included)" if t == 0 else "")
                + f"; server estimate max |diff| = {worst:.3e}, {bad} of "
                f"{total} elements outside rtol={RTOL}, atol={ATOL}")
            del ref
            if bad:
                failed.append(f"{carrier} step {t + 1}")
        log(f"[{label}] tpu_custom_call in the sharded round: "
            f"{'tpu_custom_call' in compiled.as_text()}")
        stats = [_stats(d) for d in jax.devices()]
        log(f"[{label}] per chip bytes_in_use / peak: "
            + ", ".join(f"{d.id}:{_gib(st['bytes_in_use'])}/"
                        f"{_gib(st['peak_bytes_in_use'])}"
                        for d, st in zip(jax.devices(), stats)))
        del sess, params, opt_state, ef_state, compiled
        _free()
    if failed:
        raise AssertionError("[4-chip] the sharded round's server estimate "
                             "left the vmap round's: " + ", ".join(failed))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and synthetic data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded-vs-vmap phase")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    dev, count = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        train_phase(args.seed, dev)
        serve_phase(args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)


if __name__ == "__main__":
    main()
