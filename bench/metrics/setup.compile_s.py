"""Seconds the program spent tracing, lowering and compiling (a load from
the persistent cache counts as a compile) in its own phases - the jitted
init and the step calls - each second counted once, from the program's
compile counter (``repro.telemetry.counters()``). The harness's own jits
and the reference are outside those phases. None for a program without
the counter."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    c = telemetry.counters()
    return sum(c[p]["seconds"] for p in telemetry.PHASES)
