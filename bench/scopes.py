"""Device time by layer of the train step, read from the scopes the program
gives its layers (``jax.named_scope``, src/repro/telemetry.py).

An operation's scope comes from its HLO instruction's ``op_name`` in the
compiled step: the innermost of ``SCOPES`` on that path, bare or wrapped by
a transform (``vmap(transpose(jvp(model_fwd_bwd)))/dot_general``). A fusion
with no ``op_name`` takes the majority scope of the instructions of the
computation it calls. Time is device self time (``trace.self_times``) in
the traced window per window step, averaged over the chips; what no scope
claims is ``unscoped``.
"""
from __future__ import annotations

import collections
import functools
import re
from typing import Dict, Optional

from bench import trace as T

SCOPES = ("model_fwd_bwd", "ef_client", "ef_aggregate", "downlink",
          "optimizer")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an ``op_name`` path, or None."""
    found = [t for t in re.split(r"[/()]", op_name) if t in SCOPES]
    return found[-1] if found else None


def _parse(hlo: str):
    """{instruction: (op_name or None, called computation or None)} and
    {computation: [its instructions]}."""
    instrs, comps, cur = {}, {}, None
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name = m.group(1)
            op = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            instrs[name] = (op.group(1) if op else None,
                            calls.group(1) if calls else None)
            if cur is not None:
                comps[cur].append(name)
            continue
        m = _HEADER.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
    return instrs, comps


@functools.lru_cache(maxsize=4)
def instruction_scopes(hlo: str) -> Dict[str, Optional[str]]:
    """Each instruction of compiled HLO text -> its scope (or None)."""
    instrs, comps = _parse(hlo)
    memo: Dict[str, tuple] = {}

    def vote(name: str, depth: int = 0):
        """(has a say, scope): an instruction with an op_name has a say; a
        fusion without one speaks for the majority of its computation."""
        if name in memo:
            return memo[name]
        op, calls = instrs.get(name, (None, None))
        if op is not None:
            out = (True, scope_of(op))
        elif calls in comps and depth < 8:
            votes = collections.Counter(
                s for said, s in (vote(i, depth + 1) for i in comps[calls])
                if said)
            if votes:
                # most votes; a tie goes to a scope, in SCOPES order
                out = (True, min(votes, key=lambda s: (
                    -votes[s], s is None,
                    SCOPES.index(s) if s is not None else 0)))
            else:
                out = (False, None)
        else:
            out = (False, None)
        memo[name] = out
        return out

    return {name: vote(name)[1] for name in instrs}


def step_times(run) -> Optional[dict]:
    """{"scopes": {scope: seconds per step}, "unscoped": seconds per step,
    "present": scopes some instruction carries}, or None without a trace
    or compiled step."""
    table, hlo = run["trace"], run["hlo"]
    if table is None or not table["devices"] or not hlo:
        return None
    scopes = instruction_scopes(hlo)
    w = T.window(table)
    steps = run["window"]["steps"]
    tot = collections.Counter()
    for d in table["devices"]:
        ops = T.device_ops(table, d, w)
        for (n, _, _, _), t in zip(ops, T.self_times(ops)):
            tot[scopes.get(n)] += t * 1e-9
    k = len(table["devices"]) * steps
    return {"scopes": {s: tot[s] / k for s in SCOPES},
            "unscoped": tot[None] / k,
            "present": set(scopes.values()) - {None}}


def ms_per_step(run, scope: str) -> Optional[float]:
    """Milliseconds per step of device self time under ``scope``; None when
    no instruction of the compiled step carries it."""
    t = step_times(run)
    if t is None or scope not in t["present"]:
        return None
    return 1e3 * t["scopes"][scope]
