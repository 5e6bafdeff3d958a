"""The EF aggregation's cross-chip collectives, read from the program's
``ef_collective`` scope (src/repro/telemetry.py), which it nests inside
``ef_aggregate`` around each all-reduce, all-gather or ring permute of the
clients' messages.

An instruction of the compiled step is under the scope when its
``op_name`` path holds it, bare or wrapped by a transform; a fusion with
no ``op_name`` is under it when most of the instructions of the
computation it calls are (bench/scopes.py's rule). Time is device self
time in the traced window per window step, averaged over the chips. The
operand bytes come from the program's collective counter
(``repro.telemetry.collectives``), one EF round's records.
"""
from __future__ import annotations

import functools
import re
from typing import Optional, Set

from bench import scopes
from bench import trace as T

SCOPE = "ef_collective"


def _under(op_name: str) -> bool:
    return SCOPE in re.split(r"[/()]", op_name)


@functools.lru_cache(maxsize=4)
def instructions(hlo: str) -> Set[str]:
    """Names of the compiled step's instructions under the scope."""
    instrs, comps = scopes._parse(hlo)
    memo = {}

    def vote(name: str, depth: int = 0):
        """(has a say, under the scope)."""
        if name in memo:
            return memo[name]
        op, calls = instrs.get(name, (None, None))
        if op is not None:
            out = (True, _under(op))
        elif calls in comps and depth < 8:
            said = [u for s, u in (vote(i, depth + 1) for i in comps[calls])
                    if s]
            out = (bool(said), bool(said) and 2 * sum(said) > len(said))
        else:
            out = (False, False)
        memo[name] = out
        return out

    return {name for name in instrs if vote(name)[1]}


def ms_per_step(run) -> Optional[float]:
    """Milliseconds per step of device self time under the scope; None
    without a trace, or where no instruction carries the scope."""
    table, hlo = run["trace"], run["hlo"]
    if table is None or not table["devices"] or not hlo:
        return None
    names = instructions(hlo)
    if not names:
        return None
    w = T.window(table)
    total = 0.0
    for d in table["devices"]:
        ops = T.device_ops(table, d, w)
        total += sum(t for (n, _, _, _), t in zip(ops, T.self_times(ops))
                     if n in names)
    return 1e-6 * total / (len(table["devices"]) * run["window"]["steps"])


def bytes_per_step() -> Optional[int]:
    """Operand bytes per chip of one EF round's collectives, from the
    program's counter; None for a program without it or with no
    collective recorded."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    read = getattr(telemetry, "collectives", None)
    if read is None:
        return None
    rows = read(last_round=True)
    return sum(r["bytes"] for r in rows) if rows else None
