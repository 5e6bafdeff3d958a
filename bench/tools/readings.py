"""The readings the cells' limits are set from, many seeds in one process
(set-up is long, so the program's seeds and the control's share it).

    python bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --kinds sound,control,state_bf16,unchanged,half_batch \
        [--out chiprun_out/readings.jsonl]

For each seed: the reference's first steps in float32 (once), then each
kind's side of the comparison against it, printed as one JSON line:

* ``sound``      - the program as the configuration states it;
* ``control``    - the reference itself with its matrix products' operands
                   in float8 (e4m3), one precision below the configuration's
                   bfloat16;
* ``state_bf16`` - the program with its own bfloat16 EF-state path on,
                   one precision below the configuration's float32 state;
* a fault of bench/tools/faults.py, planted under the program.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import cell as cell_lib  # noqa: E402
from bench import check, program, verify  # noqa: E402
from bench.tools import faults  # noqa: E402
from bench.tools.smoke import smoke  # noqa: E402


def program_side(cell, seed, kind):
    overrides = {"ef_state_dtype": "bfloat16"} if kind == "state_bf16" \
        else {}
    block = cell["config"]["ef"]["block"]
    with (faults.planted(kind) if kind in faults.FAULTS
          else contextlib.nullcontext()):
        sess = program.session(cell, seed, **overrides)
        got = program.first_steps(
            sess, program.round_sample(sess, seed, block))
    del sess
    verify.free_device_memory()
    return got


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default="sound")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="allow the CPU (smoke rehearsals only)")
    args = ap.parse_args()
    cell = cell_lib.load(args.workload)
    if args.cpu:
        cell = smoke(cell)
    program.import_program()
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()[:cell["workload"]["chips"]]
    if devs[0].platform != "tpu" and not args.cpu:
        sys.exit("no TPU")
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ref = verify.readings(cell, seed, devs, program.CHECK_STEPS)
        t_ref = time.perf_counter() - t
        verify.free_device_memory()
        for kind in args.kinds.split(","):
            t = time.perf_counter()
            try:
                if kind == "control":
                    got = verify.readings(cell, seed, devs,
                                          program.CHECK_STEPS,
                                          matmul_dtype="float8_e4m3fn")
                else:
                    got = program_side(cell, seed, kind)
                numbers = check.compare(got, ref, cell["config"]["ef"])
            except Exception as e:  # noqa: BLE001 - a crash is a reading
                numbers = {"error": repr(e)[:500]}
            verify.free_device_memory()
            line = {"cell": args.workload, "seed": seed, "kind": kind,
                    "seconds": time.perf_counter() - t, "ref_s": t_ref,
                    **numbers}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
