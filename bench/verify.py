"""The reference's side of the comparison: the cell's first steps followed
in plain float32 from the same seed, on the cell's chips, after the
program's state is freed."""
from __future__ import annotations

import gc
from typing import Optional

import numpy as np


def free_device_memory() -> None:
    """Drop every array still alive, so the reference starts from an
    empty chip (a process's peak never falls again)."""
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def readings(cell: dict, seed: int, devices, steps: int,
             matmul_dtype: Optional[str] = None) -> dict:
    """The same numbers ``program.first_steps`` reads, from the reference.
    ``matmul_dtype`` (e.g. ``"float8_e4m3fn"``) computes it one precision
    lower: the control."""
    import jax
    import jax.numpy as jnp

    from bench import check
    from bench.reference import data, ef21

    cfg, traffic = cell["config"], cell["traffic"]
    arch, n = cfg["arch"], cfg["clients"]
    B, S = traffic["global_batch"], traffic["seq_len"]

    def batches(t):
        toks, labs = data.batch(seed, t, B, S, arch["vocab_size"], n,
                                traffic["heterogeneity"])
        return toks.reshape(n, B // n, S), labs.reshape(n, B // n, S)

    dt = None if matmul_dtype is None else jnp.dtype(matmul_dtype)
    with jax.default_matmul_precision("highest"):
        ref = ef21.Reference(arch, cfg["ef"], n, np.asarray(devices), dt)
        out = ref.run(seed, batches, steps)
        got = {"losses": [float(x) for x in jax.device_get(out["losses"])],
               "grad": check.norms(out["s1"]),
               "update": check.diff_norms(out["p_last"], out["p0"]),
               "msg": check.diff_norms(out["s_last"], out["s1"])}
    del out
    return got
