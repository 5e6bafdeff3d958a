"""The four-chip cell on four virtual CPU devices, at the program's smoke
widths (bench/tools/smoke.py): the harness runs the sharded EF21-SGDM
round (mesh host4, one client per device) through ``Session.step_once``,
the reference follows on the same four devices, and the comparison passes
at the smoke limits and fails with every aggregated message doubled. Run in
a subprocess, so the device count never leaks into the other tests."""
import json
import os
import subprocess
import sys
import textwrap

from bench import cell as cell_lib
from bench.tests.test_bench_harness import PEAKS, SMOKE_LIMITS

FOUR_CHIPS = "smollm-360m.train.4chip.b16x1k"

SCRIPT = textwrap.dedent("""
    import json, os, sys, types
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from bench import cell as cell_lib, run
    from bench.tools import faults
    from bench.tools.smoke import smoke

    name, limits, peaks = sys.argv[1], json.loads(sys.argv[2]), \\
        json.loads(sys.argv[3])
    cell = smoke(cell_lib.load(name))
    cell["workload"]["limits"] = limits
    devs = jax.devices()[:cell["workload"]["chips"]]
    for fault in (None, "msg_altered"):
        args = types.SimpleNamespace(seed=2147483659, seconds=0.3, trace=0)
        if fault is None:
            run.measure(cell, args, devs, peaks)
        else:
            with faults.planted(fault):
                run.measure(cell, args, devs, peaks)
""")


def test_four_chip_cell_at_smoke_widths():
    assert cell_lib.load(FOUR_CHIPS)["workload"]["chips"] == 4
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([cell_lib.ROOT, os.path.join(
                   cell_lib.ROOT, "src")]))
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, FOUR_CHIPS, json.dumps(SMOKE_LIMITS),
         json.dumps(PEAKS)], cwd=cell_lib.ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    sound, doubled = [json.loads(x) for x in out.stdout.splitlines()
                      if x.startswith("{")]
    assert sound["correct"] is True, sound["checks"]
    assert sound["device"]["count"] == 4
    assert sound["failed"] == 0 and sound["attempted"] >= 1
    assert doubled["correct"] is False, doubled["checks"]
    assert doubled["checks"]["msg_gap"]["value"] \
        > doubled["checks"]["msg_gap"]["limit"]
