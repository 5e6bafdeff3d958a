"""Limits from readings (bench/tools/readings.py's JSON lines), by the
rule the cells' limits were set with:

* lower reading: the largest a sound run gives over the seeds;
* upper reading: the smallest the control gives, where that is three
  times the lower or more, or that a planted fault gives, where it is ten
  times the lower or more (three for a state left unchanged) - the least
  of these;
* limit: lower^0.4 x upper^0.6 (more room above the lower than below the
  upper), at most ten times the lower, to two significant digits.

``round_mismatch`` counts elements, so its lower reading is at least its
resolution: one element of the leaf with the fewest changed elements, the
most its runs' largest reading says about a fresh seed's.

    python bench/tools/limits.py readings.jsonl [--write <cell>]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.check import NUMBERS  # noqa: E402
from bench.tools.faults import FAULTS  # noqa: E402


def limits(rows):
    out = {}
    for n in NUMBERS:
        sound = [r[n] for r in rows if r["kind"] == "sound" and n in r]
        lower = max(sound)
        if n == "round_mismatch":
            lower = max([lower] + [r["round_resolution"] for r in rows
                                   if r["kind"] == "sound"
                                   and r.get("round_resolution")])
        uppers = {}
        for r in rows:
            if r["kind"] not in ("control", "state_bf16") + FAULTS \
                    or n not in r:
                continue
            need = 3 if r["kind"] in ("control", "state_bf16",
                                      "unchanged") else 10
            uppers.setdefault(r["kind"], []).append((r[n], need))
        cands = [min(v for v, _ in vs) for k, vs in uppers.items()
                 if min(v for v, _ in vs) >= vs[0][1] * lower]
        upper = min(cands) if cands else None
        limit = None
        if upper is not None:
            x = min(lower ** 0.4 * upper ** 0.6, 10 * lower)
            limit = float(f"{x:.2g}")
        out[n] = {"lower": lower, "upper": upper, "limit": limit,
                  "seeds": len(sound),
                  "upper_from": {k: min(v for v, _ in vs)
                                 for k, vs in uppers.items()}}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("readings")
    ap.add_argument("--write", help="cell whose workload file takes them")
    args = ap.parse_args()
    rows = [json.loads(x) for x in open(args.readings)]
    rows = [r for r in rows if "error" not in r]
    got = limits(rows)
    print(json.dumps(got, indent=1))
    if args.write:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "workloads", args.write + ".json")
        work = json.load(open(path))
        if any(v["limit"] is None or not math.isfinite(v["limit"])
               for v in got.values()):
            sys.exit("a number has no upper reading; limits not written")
        work["limits"] = {n: got[n]["limit"] for n in NUMBERS}
        with open(path, "w") as f:
            f.write(json.dumps(work, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
