"""From a profiler trace to the intervals the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small table: for each device, the operations it ran (name, start, end in
nanoseconds), and the host spans the harness opened (``bench.*``
annotations). Everything after that is interval arithmetic on the table,
so the metric readers can be checked on hand-made tables.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
# the line of a device plane that holds one event per XLA operation; each
# event is named by its HLO instruction ("%name = shape opcode(...)")
OP_LINES = ("XLA Ops",)
_INSTR = re.compile(r"%([\w.\-]+) = (.*)", re.S)
_ARRAY_SHAPE = re.compile(r"\w+\[[\d,]*\](?:\{[^}]*\})?")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _split_shape(rhs: str) -> Optional[Tuple[str, str]]:
    """``rhs`` of an HLO instruction -> (shape, opcode), or None for a line
    that is not one."""
    if rhs.startswith("("):
        depth = 0
        for i, c in enumerate(rhs):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                break
        shape, tail = rhs[:i + 1], rhs[i + 1:]
    else:
        m = _ARRAY_SHAPE.match(rhs)
        if not m:
            return None
        shape, tail = m.group(0), rhs[m.end():]
    m = re.match(r"\s*([\w\-]+)\(", tail)
    if not m:
        return None
    return shape, m.group(1)


def op_name(event_name: str) -> Tuple[str, str]:
    """(instruction, opcode) of a device op event."""
    m = _INSTR.match(event_name)
    if not m:
        return event_name, ""
    parts = _split_shape(m.group(2))
    return m.group(1), parts[1] if parts else ""


def load(path: str) -> dict:
    """{"devices": {plane: [(instruction, opcode, start_ns, end_ns), ...]},
    "host": [(span, start_ns, end_ns), ...]} from one xplane file (which
    may be gzipped)."""
    import jax

    if path.endswith(".gz"):
        with gzip.open(path) as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[tuple] = []
    for plane in data.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops.extend(op_name(e.name) + (e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    for ops in devices.values():
        ops.sort(key=lambda e: e[2])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def union(intervals) -> List[Interval]:
    """Sorted disjoint union of (start, end) intervals."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> List[Interval]:
    """The parts of the union of ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def window(table: dict) -> Interval:
    """The traced window: the harness's ``bench.window`` span."""
    spans = [(s, e) for n, s, e in table["host"] if n == "bench.window"]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return spans[0]


def device_ops(table: dict, name: str, win: Optional[Interval] = None
               ) -> List[tuple]:
    ops = table["devices"][name]
    if win is None:
        return list(ops)
    return [(n, op, max(s, win[0]), min(e, win[1])) for n, op, s, e in ops
            if min(e, win[1]) > max(s, win[0])]


def busy(table: dict, name: str, win: Interval) -> float:
    """Nanoseconds of ``win`` in which the device ran some operation."""
    return length([(s, e) for _, _, s, e in device_ops(table, name, win)])


def self_times(ops) -> List[float]:
    """Each op's duration less the time of the ops nested in it (a while
    loop's event spans its body's events), in the order of ``ops``."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    children: Dict[int, list] = {}
    stack: List[int] = []
    for i in order:
        s, e = ops[i][2], ops[i][3]
        while stack and ops[stack[-1]][3] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][3]:
            children.setdefault(stack[-1], []).append((s, e))
        stack.append(i)
    return [(op[3] - op[2]) - length(children.get(i, []))
            for i, op in enumerate(ops)]


def op_totals(table: dict, win: Interval) -> Dict[str, float]:
    """Self seconds of each operation ("instruction opcode") inside
    ``win``, averaged over the devices."""
    tot: Dict[str, float] = {}
    for name in table["devices"]:
        ops = device_ops(table, name, win)
        for (n, op, s, e), t in zip(ops, self_times(ops)):
            key = f"{n} {op}".strip()
            tot[key] = tot.get(key, 0.0) + t * 1e-9
    k = max(len(table["devices"]), 1)
    return {n: v / k for n, v in tot.items()}


def idle_gaps(table: dict, win: Interval, top: int = 10) -> List[list]:
    """The longest stretches of ``win`` in which no device ran anything,
    each named by the innermost harness span open at its start (or
    ``host:other``), as [name, seconds]."""
    busy_any = union((s, e) for name in table["devices"]
                     for _, _, s, e in device_ops(table, name, win))
    gaps = subtract([win], busy_any)
    spans = [h for h in table["host"] if h[0] != "bench.window"]
    out = []
    for s, e in gaps:
        open_at = [h for h in spans if h[1] <= s < h[2]]
        label = min(open_at, key=lambda h: h[2] - h[1])[0] if open_at \
            else "host:other"
        out.append([label, (e - s) * 1e-9])
    out.sort(key=lambda x: -x[1])
    return out[:top]


def breakdown(table: dict, win: Interval, op_names: Dict[str, str],
              top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most self
    time, each named by the JAX operation it was lowered from where the
    compiled step says (``op_names``), and the longest idle gaps."""
    ops = sorted(op_totals(table, win).items(), key=lambda kv: -kv[1])[:top]
    named = []
    for key, seconds in ops:
        where = op_names.get(key.split()[0])
        named.append([f"{key} {where[-90:]}" if where else key, seconds])
    return {"device_ops": named, "idle_gaps": idle_gaps(table, win, top)}



def op_names(hlo: str) -> Dict[str, str]:
    """Instruction name -> the ``op_name`` its metadata gives (the JAX
    operation it was lowered from), from compiled HLO text."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{'
                         r'[^}]*op_name="([^"]*)"', hlo, re.M):
        out[m.group(1)] = m.group(2)
    return out
