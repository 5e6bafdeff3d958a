"""A cell shrunk to the program's smoke widths, for CPU rehearsals and
tests: the same harness, comparison and reference, with the sizes of the
configuration's ``smoke_config()`` and a few short sequences."""
from __future__ import annotations

import copy


def smoke(cell: dict, seq_len: int = 32, rows_per_client: int = 2) -> dict:
    from bench import program
    program.import_program()
    from repro.configs import base

    cell = copy.deepcopy(cell)
    cfg = cell["config"]
    arch = base.get_smoke(cfg["run_spec"]["arch"])
    cfg["run_spec"] = dict(cfg["run_spec"], smoke=True)
    cfg["arch_overrides"] = {}
    cfg["arch"] = {"d_model": arch.d_model, "num_heads": arch.num_heads,
                   "num_kv_heads": arch.num_kv_heads,
                   "head_dim": arch.head_dim_, "d_ff": arch.d_ff,
                   "vocab_size": arch.vocab_size,
                   "num_layers": arch.num_layers, "norm_eps": arch.norm_eps,
                   "rope_theta": arch.rope_theta,
                   "sliding_window": arch.sliding_window}
    # a multi-chip spec checks its batch against the full-size mesh's
    # clients, so there the batch stays as it is
    rows = cell["traffic"]["global_batch"] if cfg["run_spec"].get("mesh") \
        == "pod" else rows_per_client * cfg["clients"]
    cell["traffic"] = dict(cell["traffic"], seq_len=seq_len, trace_steps=2,
                           global_batch=rows)
    return cell
