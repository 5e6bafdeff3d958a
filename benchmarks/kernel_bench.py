"""Kernel micro-bench: wall time of the Pallas kernels (interpret mode on CPU —
these numbers validate correctness-path overhead, NOT TPU performance; the
roofline derivation for real TPU lives in benchmarks/roofline.py) and of the
pure-JAX equivalents the models use on CPU.

Every timed section records {p10, median, p90} ns into the checked-in perf
ledger BENCH_kernels.json at the repo root (benchmarks/common.py::save_bench);
the legacy results/kernel_bench.json keeps its flat median-us keys. ``--tiny``
shrinks every geometry for the CI bench-smoke step."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (bench_run, csv_row, measure_ns, save_bench,
                               save_json)
from repro.kernels import ops, ref

# metric name -> measure_ns dict, accumulated by _bench for the ledger
_NS: dict = {}


def _bench(fn, *args, iters=3, key=None, warmup=2):
    """Time fn(*args): explicit warmup, then per-call block_until_ready
    timings (common.py::measure_ns). Returns median us for the flat legacy
    dict; the full {p10, median, p90} ns sample lands in the ledger under
    ``key``."""
    m = measure_ns(fn, *args, iters=iters, warmup=warmup)
    if key is not None:
        _NS[key] = m
    return m["median_ns"] / 1e3


def _train_step_compare(out: dict) -> None:
    """Full train-step wall time, fused vs unfused carrier (core/carriers.py):
    the SAME production path the train driver runs — one RunSpec per carrier,
    stepped through ``Session.step_once`` (launch/session.py) — dispatched
    through DenseCarrier (unfused pre→C→post chain) vs FusedPallasCarrier
    (one interpreted Pallas pass per leaf on CPU — compiled Mosaic on TPU)."""
    from benchmarks.common import bench_session

    for carrier in ("dense", "fused"):
        sess = bench_session(
            carrier=carrier, method="ef21_sgdm", compressor="block_topk",
            compressor_kw={"block": 1024, "k_per_block": 16}, eta=0.1)
        # time ONLY the jitted step on a fixed batch/state — host-side batch
        # synthesis must not dilute the fused-vs-dense device delta
        step, batch = sess.step_fn, sess.batch_for(0)
        state = [sess.params, sess.opt_state, sess.ef_state]
        key = jax.random.PRNGKey(0)

        def one(t):
            # the step donates its state: each call takes the last one's
            out = step(*state, batch, jax.random.fold_in(key, t), t)
            state[:] = out[:3]
            return out[3]

        out[f"train_step_{carrier}_us"] = _bench(
            one, 0, iters=3, key=f"train_step_{carrier}")


def _quantize_bench(out: dict, x, block: int) -> None:
    """Wire-codec wall time: Pallas block-quantize/dequantize (interpret on
    CPU) vs the jit'd jnp oracle the vmap runtimes execute."""
    d = x.size
    nb = d // block
    for bits in (8, 4):
        out[f"quantize{bits}_pallas_interp_us"] = _bench(
            lambda t, b=bits: ops.block_quantize(t, block=block, bits=b),
            x, iters=2, key=f"quantize{bits}_pallas_interp")
        out[f"quantize{bits}_ref_us"] = _bench(
            jax.jit(lambda t, b=bits: ref.block_quantize_ref(
                t.reshape(nb, block), b)), x, key=f"quantize{bits}_ref")
        q, s = ops.block_quantize(x, block=block, bits=bits)
        out[f"dequantize{bits}_pallas_interp_us"] = _bench(
            lambda a, b, bb=bits: ops.block_dequantize(
                a, b, d=d, block=block, bits=bb), q, s, iters=2,
            key=f"dequantize{bits}_pallas_interp")
        out[f"dequantize{bits}_ref_us"] = _bench(
            jax.jit(lambda a, b, bb=bits: ref.block_dequantize_ref(
                a, b, bits=bb, cols=block)), q, s,
            key=f"dequantize{bits}_ref")


def _wire_savings(out: dict, d: int, block: int, k: int) -> None:
    """Honest per-client wire words of one d-dim EF message per carrier at
    equal K (core/carriers.py::Carrier.wire_words): the x-axis the paper's
    per-bit plots use, and the collective-bytes lever --carrier buys."""
    from repro.core import carriers as carrier_lib
    from repro.core import compressors as C

    btk = C.BlockTopK(block=block, k_per_block=k)
    uplink = ("dense", "sparse", "quant8", "quant4",
              "fused_quant8", "fused_quant4")
    for name in uplink:
        out[f"wire_words_{name}"] = carrier_lib.make(name).wire_words(btk, d)
    out["wire_savings_quant8_vs_sparse"] = (
        out["wire_words_sparse"] / out["wire_words_quant8"])
    out["wire_savings_quant4_vs_sparse"] = (
        out["wire_words_sparse"] / out["wire_words_quant4"])
    # the fused carrier ships dense quantized payloads (no index words, every
    # block present) — this is the wire premium the one-launch uplink pays
    out["wire_premium_fused_quant8_vs_quant8"] = (
        out["wire_words_fused_quant8"] / out["wire_words_quant8"])
    # downlink split (DESIGN.md §8): the server broadcast per round, per
    # carrier — 'dense' is the implicit f32 broadcast every unidirectional
    # runtime ships, the lever --downlink-carrier attacks (acceptance: the
    # quant4 broadcast undercuts dense by well over 7×)
    for name in uplink:
        out[f"downlink_words_{name}"] = carrier_lib.downlink_words(
            carrier_lib.make(name), btk, d)
    for name in ("sparse", "quant8", "quant4"):
        out[f"downlink_savings_{name}_vs_dense"] = (
            out["downlink_words_dense"] / out[f"downlink_words_{name}"])


def _schedule_wire(out: dict) -> None:
    """Mixed-schedule wire accounting (DESIGN.md §9): dense norms/biases +
    quant4 embeds + sparse attention/MLP over the real (smoke) smollm param
    tree, per group and in total, against the uniform BlockTopK baseline —
    the scenario lever per-group schedules buy over any single-knob config."""
    import jax

    from repro.configs import base as cb
    from repro.core import compressors as C
    from repro.core import ef as ef_lib
    from repro.core import schedule as sched_lib
    from repro.models import model as model_lib

    cfg = cb.get_smoke("smollm-360m")
    shapes = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    btk = C.BlockTopK(ratio=0.05)
    method = ef_lib.EF21SGDM(compressor=btk, eta=0.1)
    mixed = sched_lib.CompressionSchedule((
        sched_lib.Group(pattern="norm|bias", compressor=C.Identity(),
                        carrier="dense"),
        sched_lib.Group(pattern="embed", compressor=btk, carrier="quant4"),
        sched_lib.Group(pattern="*", compressor=C.BlockTopK(ratio=0.02),
                        carrier="sparse"),
    ))
    uniform = sched_lib.CompressionSchedule.uniform(btk, carrier="sparse")
    per, total = sched_lib.wire_words_tree(mixed, method, shapes, "up")
    for grp, words in zip(mixed.groups, per):
        out[f"sched_wire_up_{grp.pattern.replace('|', '_')}"] = words
    out["sched_wire_up_mixed_total"] = total
    _, out["sched_wire_up_uniform_total"] = sched_lib.wire_words_tree(
        uniform, method, shapes, "up")
    out["sched_mixed_vs_uniform"] = (
        out["sched_wire_up_uniform_total"] / max(total, 1e-9))


def run(tiny: bool = False) -> dict:
    rng = np.random.RandomState(0)
    out = {}
    _NS.clear()

    # --tiny shrinks every geometry so the CI bench-smoke step exercises the
    # full codepath (incl. the ledger write) in seconds; the numbers it
    # records are labelled by their geometry, never compared across modes.
    S = 128 if tiny else 512
    d = 1 << 14 if tiny else 1 << 20
    block, k = (256, 8) if tiny else (1024, 16)

    B, H, hd = 1, 4, 64
    q, kk, v = [jnp.asarray(rng.randn(B, S, H, hd), jnp.float32)
                for _ in range(3)]
    out["flash_pallas_interp_us"] = _bench(
        lambda a, b, c: ops.flash_attention(a, b, c, block_q=128, block_k=128),
        q, kk, v, iters=2, key="flash_pallas_interp")
    out["flash_ref_us"] = _bench(
        jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c)), q, kk, v,
        key="flash_ref")

    x = jnp.asarray(rng.randn(d).astype(np.float32))
    out["block_topk_pallas_interp_us"] = _bench(
        lambda t: ops.block_topk(t, block=block, k=k), x, iters=2,
        key="block_topk_pallas_interp")
    out["block_topk_ref_us"] = _bench(
        jax.jit(lambda t: ref.block_topk_ref(t, block, k)), x,
        key="block_topk_ref")

    g, vv, gg = [jnp.asarray(rng.randn(d).astype(np.float32))
                 for _ in range(3)]
    out["ef_update_fused_interp_us"] = _bench(
        lambda a, b, c: ops.ef21_sgdm_update(a, b, c, eta=0.1), g, vv, gg,
        iters=2, key="ef_update_fused_interp")
    out["ef_update_ref_us"] = _bench(
        jax.jit(lambda a, b, c: ref.ef21_sgdm_update_ref(
            a, b, c, eta=0.1, block=block, k=k)), g, vv, gg,
        key="ef_update_ref")

    # the one-launch uplink mega-kernel vs its composed jnp oracle (both
    # interpret-path on CPU — differential overhead only; the honest speedup
    # claim lives in benchmarks/fused_round_bench.py on the compiled path)
    out["fused_uplink_pallas_interp_us"] = _bench(
        lambda a, b, c: ops.ef21_sgdm_topk_quant(
            a, b, c, eta=0.1, block=block, k=k, bits=8), g, vv, gg, iters=2,
        key="fused_uplink_pallas_interp")
    out["fused_uplink_ref_us"] = _bench(
        jax.jit(lambda a, b, c: ref.ef21_sgdm_topk_quant_ref(
            a, b, c, eta=0.1, block=block, k=k, bits=8)), g, vv, gg,
        key="fused_uplink_ref")

    _quantize_bench(out, x, block)
    _wire_savings(out, d, block, k)
    if not tiny:
        _schedule_wire(out)
        _train_step_compare(out)

    save_json("kernel_bench", out)
    speedups = {"ef_update_ref_vs_fused_uplink_ref": (
        _NS["ef_update_ref"]["median_ns"]
        / max(_NS["fused_uplink_ref"]["median_ns"], 1))}
    if "train_step_dense" in _NS:
        speedups["train_step_fused_vs_dense"] = (
            _NS["train_step_dense"]["median_ns"]
            / max(_NS["train_step_fused"]["median_ns"], 1))
    ledger = save_bench("kernels", bench_run(
        geometry={"d": d, "block": block, "k_per_block": k, "bits": [8, 4],
                  "flash": {"B": B, "S": S, "H": H, "hd": hd},
                  "tiny": tiny},
        metrics=_NS, speedup_vs_ref=speedups))
    out["bench_ledger"] = ledger
    step = ("" if tiny else
            f"step_dense_us={out['train_step_dense_us']:.0f};"
            f"step_fused_us={out['train_step_fused_us']:.0f};")
    csv_row("kernel_bench", out["flash_pallas_interp_us"],
            f"topk_ref_us={out['block_topk_ref_us']:.0f};"
            f"ef_ref_us={out['ef_update_ref_us']:.0f};"
            f"fused_uplink_ref_us={out['fused_uplink_ref_us']:.0f};" + step +
            f"wire_q8_x={out['wire_savings_quant8_vs_sparse']:.1f};"
            f"wire_q4_x={out['wire_savings_quant4_vs_sparse']:.1f};"
            f"down_q4_x={out['downlink_savings_quant4_vs_dense']:.1f}")
    return out


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tiny", action="store_true",
                   help="CI bench-smoke geometry: shrink every size so the "
                        "full codepath (incl. the BENCH ledger write) runs "
                        "in seconds")
    run(tiny=p.parse_args().tiny)
