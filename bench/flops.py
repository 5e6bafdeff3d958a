"""Operations and bytes computed from shapes alone.

``model_flops_per_token`` is what MFU divides by: the forward and backward
passes' matrix products, 6 x parameters (the tied embedding counted once,
as the output head; the lookup is free) plus causal attention, 6 x 2 x
(S/2 average keys) x heads x head_dim per layer. Recomputation under remat
is not counted.

``uplink_bytes`` is the least HBM traffic of the fused EF21-SGDM uplink
kernel (``kernels/fused_round.py::ef21_sgdm_topk_quant``): it reads the
gradient, v and g once, writes v', g' and the int8 wire, and one f32 scale
per selection block. Padding the kernel adds is not counted, so the count
is a lower bound.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

# the kernel's Block-TopK bisection: 26 iterations of compare + count per
# element, plus the momentum update, selection and codec (about 12 more)
BISECT_ITERS = 26
UPLINK_OPS_PER_ELEMENT = 2 * BISECT_ITERS + 12


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Leaf shapes of the dense decoder's parameters (layers stacked)."""
    d, H, KV, hd, ff, V, L = (cfg["d_model"], cfg["num_heads"],
                              cfg["num_kv_heads"], cfg["head_dim"],
                              cfg["d_ff"], cfg["vocab_size"],
                              cfg["num_layers"])
    return {
        "embed": (V, d), "final_norm": (d,),
        "layers/attn/wq": (L, d, H, hd), "layers/attn/wk": (L, d, KV, hd),
        "layers/attn/wv": (L, d, KV, hd), "layers/attn/wo": (L, H, hd, d),
        "layers/attn/norm": (L, d),
        "layers/mlp/w_gate": (L, d, ff), "layers/mlp/w_up": (L, d, ff),
        "layers/mlp/w_down": (L, ff, d), "layers/mlp/norm": (L, d),
    }


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 6 * 2 * (seq_len / 2) * cfg["num_heads"] * cfg["head_dim"] \
        * cfg["num_layers"]
    return 6.0 * param_count(cfg) + attn


def block_geom(d: int, block: int, ratio: float) -> Tuple[int, int, int]:
    """(blocks, block width, kept per block) of Block-TopK on a leaf of d
    elements: a leaf under one block is one block of its own size."""
    b = min(block, d)
    return -(-d // b), b, max(1, min(b, int(round(ratio * b))))


def uplink_bytes(cfg: dict, clients: int, block: int, ratio: float,
                 grad_bytes: int = 4, state_bytes: int = 4,
                 wire_bits: int = 8) -> float:
    """Least HBM bytes of one step's uplink kernel launches for
    ``clients`` clients on one chip."""
    total = 0.0
    for shape in param_shapes(cfg).values():
        d = math.prod(shape)
        nb, _, _ = block_geom(d, block, ratio)
        total += d * (grad_bytes + 4 * state_bytes + wire_bits / 8) + 4 * nb
    return clients * total


def uplink_ops(cfg: dict, clients: int) -> float:
    return clients * UPLINK_OPS_PER_ELEMENT * param_count(cfg)
