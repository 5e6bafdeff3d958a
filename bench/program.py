"""The system under test, driven the way a user trains with it:
``RunSpec`` -> ``Session`` -> ``Session.step_once``, back to back.

Everything here touches the program only through that entry; the cut in
depth a configuration names is applied to ``Session.cfg`` before the
training state exists, since the session builds that state lazily from it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import sys
import time
from typing import List, Optional

import numpy as np

from bench.cell import ROOT

# the steps the reference follows: two, so that its run (about two thirds
# of three steps' time) stays near the window's length
CHECK_STEPS = 2
# whole selection blocks per leaf whose EF round the reference repeats on
# the program's own state (bench/check.py, ``round_mismatch``)
ROUND_BLOCKS = 64


def import_program():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)


def session(cell: dict, seed: int, **spec_overrides):
    """The cell's Session at ``seed``, with its sizes checked against the
    configuration file."""
    import_program()
    from repro.launch.session import Session
    from repro.launch.spec import RunSpec

    cfg, traffic = cell["config"], cell["traffic"]
    fields = dict(cfg["run_spec"], seq_len=traffic["seq_len"],
                  global_batch=traffic["global_batch"],
                  heterogeneity=traffic["heterogeneity"], seed=int(seed))
    fields.update(spec_overrides)
    sess = Session(RunSpec(**fields))
    if cfg.get("arch_overrides"):
        sess.cfg = dataclasses.replace(sess.cfg, **cfg["arch_overrides"])
    arch, ef = cfg["arch"], cfg["ef"]
    got = {"d_model": sess.cfg.d_model, "num_heads": sess.cfg.num_heads,
           "num_kv_heads": sess.cfg.num_kv_heads,
           "head_dim": sess.cfg.head_dim_, "d_ff": sess.cfg.d_ff,
           "vocab_size": sess.cfg.vocab_size,
           "num_layers": sess.cfg.num_layers,
           "norm_eps": sess.cfg.norm_eps, "rope_theta": sess.cfg.rope_theta,
           "sliding_window": sess.cfg.sliding_window}
    want = {k: arch[k] for k in got}
    if got != want:
        raise ValueError(f"the program runs {got}, the configuration file "
                         f"says {want}")
    if sess.n_clients != cfg["clients"]:
        raise ValueError(f"{sess.n_clients} clients, the configuration "
                         f"says {cfg['clients']}")
    method = sess.method
    have = {"eta": method.eta, "ratio": method.compressor.ratio,
            "block": method.compressor.block, "lr": sess.spec.lr,
            "bits": int(sess.spec.carrier[-1])}
    if any(have[k] != ef[k] for k in have):
        raise ValueError(f"the program's EF settings {have} differ from the "
                         f"configuration file's {ef}")
    return sess


def check_precision(sess, cfg: dict) -> None:
    """Refuse a session whose compute, parameter or EF-state dtypes are not
    the ones the configuration file's ``precision`` names."""
    import jax

    def dtypes(tree):
        return sorted({str(x.dtype) for x in jax.tree_util.tree_leaves(tree)})

    got = {"matmuls_and_activations": [str(sess.cfg.dtype)],
           "parameters": dtypes(sess.params),
           "ef_state": dtypes(sess.ef_state)}
    want = {k: [cfg["precision"][k]] for k in got}
    if got != want:
        raise ValueError(f"the program runs dtypes {got}, the configuration "
                         f"file says {want}")


def round_sample(sess, seed: int, block: int,
                 blocks: int = ROUND_BLOCKS) -> List[dict]:
    """Per parameter leaf, ``blocks`` whole selection blocks drawn from the
    seed: the leaf's name, the index of each of their elements into the
    leaf's shape (an element past the leaf's end repeats its last one),
    which of them lie inside the leaf, and the block width."""
    import jax

    from bench import check

    rng = np.random.default_rng(seed)
    server = sess.ef_state["server"]
    out = []
    for name, x in zip(check.leaf_names(server),
                       jax.tree_util.tree_leaves(server)):
        d = x.size
        b = min(block, d)
        nb = -(-d // b)
        ids = np.sort(rng.choice(nb, size=min(blocks, nb), replace=False))
        flat = (ids[:, None] * b + np.arange(b)).reshape(-1)
        index = np.unravel_index(np.minimum(flat, d - 1), x.shape)
        out.append({"leaf": name,
                    "index": tuple(i.astype(np.int32) for i in index),
                    "inside": flat < d, "block": b})
    return out


def _take(state, index):
    """The sampled elements of the client states v, g and of the server
    estimate, in float32: (clients, elements) and (elements,) per leaf."""
    import jax
    import jax.numpy as jnp

    def pick(tree):
        return [x[(Ellipsis,) + ix].astype(jnp.float32)
                for x, ix in zip(jax.tree_util.tree_leaves(tree), index)]
    return {"v": pick(state["clients"]["v"]),
            "g": pick(state["clients"]["g"]),
            "server": pick(state["server"])}


def annotate(sess):
    """Host spans around the session's batch preparation, on the profiler's
    clock (an instance attribute, so ``step_once`` picks it up)."""
    import jax

    inner = sess.batch_for

    def batch_for(step):
        with jax.profiler.TraceAnnotation("bench.batch"):
            return inner(step)

    sess.batch_for = batch_for


def first_steps(sess, sample: List[dict],
                steps: int = CHECK_STEPS) -> dict:
    """Take the first ``steps`` steps through ``step_once`` and keep what
    the comparison reads: each step's loss; per leaf the norms of the
    server estimate after step 1, of the parameters' change, and of the
    server estimate's change after step 1; and the ``sample``
    (``round_sample``) of the EF state before the first step and after
    each (``rounds``, on the host)."""
    import jax
    import jax.numpy as jnp

    from bench import check

    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    take = jax.jit(_take)
    index = [s["index"] for s in sample]
    p0 = copy(sess.params)
    rounds = [take(sess.ef_state, index)]
    losses, s1 = [], None
    for t in range(steps):
        m = sess.step_once()
        losses.append(m["loss"])
        rounds.append(take(sess.ef_state, index))
        if t == 0:
            s1 = copy(sess.ef_state["server"])
    out = {"losses": [float(x) for x in jax.device_get(losses)],
           "grad": check.norms(s1),
           "update": check.diff_norms(sess.params, p0),
           "msg": check.diff_norms(sess.ef_state["server"], s1),
           "rounds": jax.device_get(rounds), "sample": sample}
    del p0, s1
    return out


def run_window(sess, seconds: float, steps: Optional[int] = None) -> dict:
    """Step back to back until ``seconds`` have passed (or ``steps`` steps
    are done), one step queued behind the one running; the window ends when
    the last step's outputs are ready. ``ready_s`` holds the second at which
    each step's loss was read, from the window's start."""
    import jax

    n, losses, ready, prev = 0, [], [], None
    span = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with span("bench.window"):
        while True:
            with span("bench.step_once"):
                m = sess.step_once()
            n += 1
            if prev is not None:
                losses.append(float(prev["loss"]))
                ready.append(time.perf_counter() - t0)
            prev = m
            if steps is not None and n >= steps:
                break
            if steps is None and time.perf_counter() - t0 >= seconds:
                break
        losses.append(float(prev["loss"]))
    t1 = time.perf_counter()
    ready.append(t1 - t0)
    return {"steps": n, "seconds": t1 - t0, "losses": losses,
            "ready_s": ready,
            "failed": sum(not math.isfinite(x) for x in losses)}


def compiled_text(sess) -> str:
    """HLO text of the compiled step the window ran."""
    import jax

    ambient = sess.mesh_context() if sess.mesh.size > 1 \
        else contextlib.nullcontext()
    with ambient:
        return sess.step_fn.lower(
            sess.params, sess.opt_state, sess.ef_state,
            sess.batch_for(sess.step), jax.random.PRNGKey(0),
            sess.step).compile().as_text()
