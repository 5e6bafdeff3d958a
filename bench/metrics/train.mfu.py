"""Model FLOP utilization of the traced window: model FLOPs per token
(bench/flops.py, remat recompute not counted) times the window's tokens
per second, over the chips' bf16 peak."""
from bench import flops
from bench import trace as T


def read(run):
    table = run["trace"]
    if table is None or not table["devices"]:
        return None
    w = T.window(table)
    cfg = run["cell"]["config"]["arch"]
    seq = run["cell"]["traffic"]["seq_len"]
    tok_s = run["window"]["steps"] * run["tokens_per_step"] \
        / ((w[1] - w[0]) * 1e-9)
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops.model_flops_per_token(cfg, seq) * tok_s / peak
