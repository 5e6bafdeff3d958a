"""A cell, found by name: its workload file, its configuration and its
traffic, and the metrics ``BENCHMARK.json`` asks of it.

    bench/workloads/<cell>.json   configuration, traffic, chips, limits
    bench/configs/<config>.json   the sizes as run, the source, the cuts
    bench/traffic/<traffic>.json  batch, sequence, data mix
    bench/metrics/<metric>.py     one reader per metric: read(run)
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT, "BENCHMARK.json")


def load(name: str) -> dict:
    """{"name", "workload", "config", "traffic"} of one cell."""
    work = _json(BENCH, "workloads", name + ".json")
    return {"name": name, "workload": work,
            "config": _json(BENCH, "configs", work["config"] + ".json"),
            "traffic": _json(BENCH, "traffic", work["traffic"] + ".json")}


def metrics(name: str, trace: bool) -> List[dict]:
    """The entries of BENCHMARK.json that this cell reports: end-to-end ones
    without a trace, per-layer ones with it."""
    spec = benchmark()
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries if name in m.get("workloads", [name])]


def reader(metric: str) -> Callable:
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, float]:
    table = _json(BENCH, "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table["devices"][device_kind]
