"""The benchmark's entry: one cell, one seed, one process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from process start): the cell's Session from
the seed, its jitted init, its dtypes checked against the configuration's
``precision``, and the first two steps through ``Session.step_once`` -
they compile (or load from the persistent cache) the one step program the
window runs, and give the comparison its readings, among them sampled
blocks of the EF state around each step. The window then steps back to back for ``--seconds``. With
``--trace 1`` a few steps of the window run under the profiler and the
per-layer metrics are read from that trace and the compiled step.

Once the window has closed and the program's state is freed, a plain
float32 reference follows the same two steps; ``correct`` is the
comparison of the two (bench/check.py) against the cell's limits. The last
line of standard output is one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error and the last key
of that object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import cell as cell_lib  # noqa: E402


def fail(msg: str, code: int = 2):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def devices_for(chips: int):
    """The first ``chips`` TPU chips; exits when JAX finds fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r}); the "
             "benchmark runs on the chip only")
    if len(devs) < chips:
        fail(f"the cell needs {chips} TPU chips, JAX found {len(devs)}")
    return devs[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cell_lib.load(args.workload)
    except FileNotFoundError as e:
        fail(f"unknown cell {args.workload!r}: {e}")
    chips = cell["workload"]["chips"]

    from bench import program
    try:
        program.import_program()
    except FileNotFoundError as e:
        fail(str(e))
    import jax
    devs = devices_for(chips)
    try:
        peaks = cell_lib.peaks(devs[0].device_kind)
    except KeyError as e:
        fail(str(e))
    from repro.launch import compile_cache
    compile_cache.enable()
    # every program of a run, small ones too, comes from the cache after
    # the cell's first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return measure(cell, args, devs, peaks)


def measure(cell: dict, args, devs, peaks: dict) -> int:
    """Set-up, window and comparison of one run on ``devs``; prints the
    result line."""
    import jax

    from bench import check, program, verify
    from bench import trace as trace_lib

    work, traffic, ef = cell["workload"], cell["traffic"], cell["config"]["ef"]
    marks = {"imported": time.perf_counter() - T_START}
    sess = program.session(cell, args.seed)
    program.annotate(sess)
    jax.block_until_ready(sess.params)
    marks["initialised"] = time.perf_counter() - T_START
    program.check_precision(sess, cell["config"])
    got = program.first_steps(
        sess, program.round_sample(sess, args.seed, ef["block"]))
    setup_s = time.perf_counter() - T_START

    trace_table, hlo = None, None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans, not every call
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        win = program.run_window(sess, args.seconds,
                                 steps=traffic["trace_steps"])
        jax.profiler.stop_trace()
        trace_table = trace_lib.load(trace_lib.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        hlo = program.compiled_text(sess)
    else:
        win = program.run_window(sess, args.seconds)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    del sess
    verify.free_device_memory()

    t_ref = time.perf_counter()
    ref = verify.readings(cell, args.seed, devs, program.CHECK_STEPS)
    t_ref = time.perf_counter() - t_ref
    numbers = check.compare(got, ref, ef)
    limits = work["limits"]
    correct = check.verdict(numbers, limits) and win["failed"] == 0

    run = {"cell": cell, "chips": len(devs),
           "device_kind": devs[0].device_kind,
           "peaks": peaks,
           "tokens_per_step": traffic["global_batch"] * traffic["seq_len"],
           "window": win, "setup_s": setup_s, "trace": trace_table,
           "hlo": hlo}
    metrics = {}
    for m in cell_lib.metrics(cell["name"], bool(args.trace)):
        value = cell_lib.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "device_kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": win["steps"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if trace_table is not None and trace_table["devices"]:
        w = trace_lib.window(trace_table)
        busy = [trace_lib.busy(trace_table, d, w)
                for d in trace_table["devices"]]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (w[1] - w[0]) * 1e-9
        result["breakdown"] = trace_lib.breakdown(
            trace_table, w, trace_lib.op_names(hlo or ""))
    result["notes"] = {"setup_marks_s": marks, "reference_s": t_ref,
                       "window_steps": win["steps"],
                       "window_s": win["seconds"],
                       "window_ready_s": win["ready_s"],
                       "worst_leaves": {k: numbers[k] for k in numbers
                                        if k.endswith("_leaf")},
                       "left_out": numbers["left_out"],
                       "round_resolution": numbers["round_resolution"],
                       "losses": got["losses"], "ref_losses": ref["losses"],
                       "window_losses": win["losses"]}
    result["checks"] = {n: {"value": numbers[n], "limit": limits.get(n)}
                        for n in check.NUMBERS}
    print(json.dumps(result), flush=True)
    for n in check.NUMBERS:
        print(f"[check] {n} {numbers[n]!r} limit {limits.get(n)!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
