"""Share of its roofline that the fused EF21-SGDM uplink kernel
(``kernels/fused_round.py::ef21_sgdm_topk_quant``, one Mosaic launch per
parameter leaf) reaches: its least time per step - the larger of its
bytes over HBM bandwidth and its operations over the bf16 peak
(bench/flops.py) - over the device time its launches take per step.

Its launches are the step's ``tpu_custom_call`` ops whose outputs are the
kernel's (v', g', int8 wire, scales); their trace events carry those op
names."""
import re

from bench import flops
from bench import trace as T

_L = r"(?:\{[^}]*\})?"
_LAUNCH = re.compile(
    r"%([\w.\-]+) = \(f32\[\d+,\d+\]" + _L + r", f32\[\d+,\d+\]" + _L
    + r", [su]8\[\d+,\d+\]" + _L + r", f32\[\d+,1\]" + _L
    + r"\) custom-call\(.*custom_call_target=\"tpu_custom_call\"")


def launches(hlo: str):
    return {m.group(1) for m in map(_LAUNCH.search, hlo.splitlines()) if m}


def read(run):
    table, hlo = run["trace"], run["hlo"]
    if table is None or not hlo:
        return None
    names = launches(hlo)
    if not names or not table["devices"]:
        return None
    w = T.window(table)
    steps = run["window"]["steps"]
    per_chip = [sum(e - s for n, _, s, e in T.device_ops(table, d, w)
                    if n in names) * 1e-9 / steps
                for d in table["devices"]]
    kernel_s = sum(per_chip) / len(per_chip)
    if kernel_s <= 0:
        return None
    cfg = run["cell"]["config"]
    clients = cfg["clients"] // run["chips"]
    ef = cfg["ef"]
    least = max(flops.uplink_bytes(cfg["arch"], clients, ef["block"],
                                   ef["ratio"])
                / run["peaks"]["hbm_bytes_per_s"],
                flops.uplink_ops(cfg["arch"], clients)
                / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
