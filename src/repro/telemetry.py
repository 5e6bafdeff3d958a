"""The program's own tracing: the names of the train step's layers, host
spans on the profiler's clock, and a count of compiles per program phase.

Device scopes. The train step names its layers with ``jax.named_scope``:
``MODEL`` (every client's forward and backward), ``EF_CLIENT`` (the
clients' EF update, compression and wire encode), ``EF_AGGREGATE`` (the
decode, the mean over clients or its collective, the server step and the
norm of its estimate that the step reports), ``DOWNLINK`` (the server's
compressed broadcast) and ``OPTIMIZER``. Both runtimes (``ef_round`` and
``ef_round_sharded``) use the same names. A scope lands in the ``op_name``
metadata of every HLO instruction lowered under it, as a path component
that a transform may wrap (``vmap(transpose(jvp(model_fwd_bwd)))``), so
the ops of a profiler trace can be put down to a layer; it costs nothing
at run time. Each Pallas kernel carries its own ``name=`` in the same
path. The cross-chip collectives of ``ef_round_sharded``'s aggregation
(the clients' all-reduces and all-gathers, the intra- and cross-pod
means) run under ``EF_COLLECTIVE``, nested inside ``EF_AGGREGATE``; it is
not one of ``SCOPES``, so a split of the step by layer still counts them
in the aggregation. Attention, on either of its paths, runs under
``ATTENTION``, nested inside ``MODEL`` in the same way: ``MODEL``'s time
still counts it.

Host spans. ``span(name)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` and ``step_span(n)`` the ``repro.step`` step annotation;
``Session`` opens ``repro.step`` around ``step_once`` and, inside it,
``repro.batch``, ``repro.dispatch`` and ``repro.publish``, and
``repro.init`` around the jitted init. With no profiler running a span is
one check of the profiler's state.

Compile counter. One ``jax.monitoring`` listener counts, per program phase
(``phase("init")`` and ``phase("step")``, which ``Session`` opens around
the jitted init and the step call), the backend compiles (loads from the
persistent cache among them), the seconds spent tracing, lowering and
compiling, and the persistent cache's hits and misses. Compiles outside a
phase (another program's, a reference's) are not counted. A backend
compile in phase ``step`` after a session's first step is a recompile, and
is kept with its step number. ``counters()`` returns a plain snapshot.

Collectives. Each such collective records, each time it is traced, its
operation, mesh axes and operand size on one chip
(``record_collective``); ``ef_round_sharded`` opens a new round of records
each time it is traced (``collective_round``), so ``collectives()`` gives
one step's collectives as the records of one round.

Kernel tiles. A Pallas kernel whose row tile follows its shape records,
each time it is traced, its launch's rows, block, row tile and grid steps
(``record_tile``); ``tiles()`` returns them in trace order.

Attention calls. Each attention call records, each time it is traced, the
path it took (``"kernel"``: the fused flash kernel; ``"chunked"``: the jnp
query-chunk scan; ``"decode"``: one token against its cache), its shape and
the kernel's block (``record_attention``); ``attention_calls()`` returns
them in trace order.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

MODEL = "model_fwd_bwd"
EF_CLIENT = "ef_client"
EF_AGGREGATE = "ef_aggregate"
DOWNLINK = "downlink"
OPTIMIZER = "optimizer"
SCOPES = (MODEL, EF_CLIENT, EF_AGGREGATE, DOWNLINK, OPTIMIZER)
EF_COLLECTIVE = "ef_collective"
ATTENTION = "attention"

SPAN_PREFIX = "repro."
PHASES = ("init", "step")

# jax.monitoring's event names (jax/_src/dispatch.py, jax/_src/compiler.py)
_KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}


def span(name: str):
    """A host span ``repro.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def step_span(step: int):
    """The ``repro.step`` span of training step ``step``."""
    return jax.profiler.StepTraceAnnotation(SPAN_PREFIX + "step",
                                            step_num=int(step))


def _seconds(intervals) -> float:
    """Length of the union of (start, end) intervals: a trace that calls
    another jitted function reports both, nested."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


class CompileCounter:
    """Compiles, their seconds and the persistent cache's hits and misses,
    per program phase. Events arrive from ``jax.monitoring`` on the thread
    that compiles; the phase is that thread's."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._spans = {p: {k: [] for k in _KINDS.values()} for p in PHASES}
        self._counts = {p: {"compiles": 0, "cache_hits": 0,
                            "cache_misses": 0} for p in PHASES}
        self._recompiles: List[dict] = []

    @contextlib.contextmanager
    def phase(self, name: str, step: Optional[int] = None,
              warm: bool = False):
        """Count what compiles inside as phase ``name``. ``warm`` marks a
        step after the session's first: a backend compile there is a
        recompile at ``step``."""
        if name not in PHASES:
            raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
        prev = getattr(self._local, "phase", None)
        self._local.phase = (name, step, warm)
        try:
            yield
        finally:
            self._local.phase = prev

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        kind = _KINDS.get(event)
        cur = getattr(self._local, "phase", None)
        if kind is None or cur is None:
            return
        name, step, warm = cur
        with self._lock:
            self._spans[name][kind].append((start, end))
            if kind == "compile":
                self._counts[name]["compiles"] += 1
                if name == "step" and warm:
                    self._recompiles.append(
                        {"step": step, "seconds": end - start,
                         "fun": str(kw.get("fun_name", ""))})

    def on_event(self, event: str, **kw) -> None:
        key = _CACHE.get(event)
        cur = getattr(self._local, "phase", None)
        if key is None or cur is None:
            return
        with self._lock:
            self._counts[cur[0]][key] += 1

    def recompiles(self) -> List[dict]:
        with self._lock:
            return [dict(r) for r in self._recompiles]

    def snapshot(self) -> Dict:
        """{phase: {"compiles", "cache_hits", "cache_misses", "trace_s",
        "lower_s", "compile_s", "seconds"}, "recompiles": [{"step",
        "seconds", "fun"}]}; ``seconds`` is the time spent in any of
        tracing, lowering and compiling, each second counted once."""
        with self._lock:
            out: Dict = {}
            for p in PHASES:
                spans = self._spans[p]
                row = dict(self._counts[p])
                for kind, iv in spans.items():
                    row[kind + "_s"] = _seconds(iv)
                row["seconds"] = _seconds(
                    [x for iv in spans.values() for x in iv])
                out[p] = row
            out["recompiles"] = [dict(r) for r in self._recompiles]
            return out


# jax.monitoring's listeners are the process's, so its counter is too
COUNTER = CompileCounter()
_installed = False
_install_lock = threading.Lock()


def install() -> None:
    """Register ``COUNTER``'s listeners with ``jax.monitoring``, once."""
    global _installed
    with _install_lock:
        if _installed:
            return
        jax.monitoring.register_event_time_span_listener(COUNTER.on_span)
        jax.monitoring.register_event_listener(COUNTER.on_event)
        _installed = True


def phase(name: str, step: Optional[int] = None, warm: bool = False):
    """``COUNTER.phase``, with the listeners installed."""
    install()
    return COUNTER.phase(name, step=step, warm=warm)


def counters() -> Dict:
    """A snapshot of the process's compile counter (``CompileCounter.
    snapshot``)."""
    return COUNTER.snapshot()


def recompiles() -> List[dict]:
    """Each recompile so far: its step, seconds and function name."""
    return COUNTER.recompiles()


_TILES: List[dict] = []
_tiles_lock = threading.Lock()


def record_tile(kernel: str, *, rows: int, block: int, rt: int) -> None:
    """Record one traced launch of ``kernel``: ``rows`` × ``block`` as
    launched (padding rows included), ``rt`` rows per grid step."""
    with _tiles_lock:
        _TILES.append({"kernel": kernel, "rows": int(rows),
                       "block": int(block), "rt": int(rt),
                       "steps": -(-int(rows) // int(rt))})


def tiles(kernel: Optional[str] = None) -> List[dict]:
    """Every launch recorded so far (of ``kernel`` alone, if given):
    {"kernel", "rows", "block", "rt", "steps"}, in trace order."""
    with _tiles_lock:
        return [dict(t) for t in _TILES
                if kernel is None or t["kernel"] == kernel]


_COLLECTIVES: List[dict] = []
_rounds = 0
_collectives_lock = threading.Lock()


def collective_round() -> int:
    """Open the next round of recorded collectives and return its number:
    the records that follow, until the next call, are one trace of one EF
    round."""
    global _rounds
    with _collectives_lock:
        _rounds += 1
        return _rounds


def record_collective(op: str, axes: Sequence[str], elements: int,
                      dtype) -> None:
    """Record one traced collective ``op`` over mesh ``axes`` whose operand
    holds ``elements`` of ``dtype`` on each chip."""
    if isinstance(axes, str):
        axes = (axes,)
    with _collectives_lock:
        _COLLECTIVES.append({
            "op": op, "axes": tuple(axes), "elements": int(elements),
            "bytes": int(elements) * np.dtype(dtype).itemsize,
            "round": _rounds})


def collectives(last_round: bool = False) -> List[dict]:
    """Every collective recorded so far (of the latest round alone, if
    ``last_round``): {"op", "axes", "elements", "bytes", "round"}, in trace
    order."""
    with _collectives_lock:
        return [dict(c) for c in _COLLECTIVES
                if not last_round or c["round"] == _rounds]


_ATTENTION: List[dict] = []
_attention_lock = threading.Lock()


def record_attention(path: str, *, B: int, S: int, H: int, KV: int,
                     hd: int, block: Optional[int] = None
                     ) -> None:
    """Record one traced attention call that took ``path`` (``"kernel"``,
    ``"chunked"`` or ``"decode"``) on ``B`` sequences of ``S`` tokens, ``H``
    query and ``KV`` key/value heads of ``hd``; ``block`` is the kernel's
    query and key block, in its forward, dK/dV and dQ passes."""
    with _attention_lock:
        _ATTENTION.append({"path": path, "B": int(B), "S": int(S),
                           "H": int(H), "KV": int(KV), "hd": int(hd),
                           "block": None if block is None
                           else int(block)})


def attention_calls() -> List[dict]:
    """Every attention call recorded so far: {"path", "B", "S", "H", "KV",
    "hd", "block"}, in trace order."""
    with _attention_lock:
        return [dict(a) for a in _ATTENTION]


def clear_attention_calls() -> None:
    """Forget the attention calls recorded so far."""
    with _attention_lock:
        _ATTENTION.clear()
