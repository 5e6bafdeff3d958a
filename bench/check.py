"""The comparison that decides ``correct``.

Both the program and the reference follow the same first steps from the
seed. Four numbers are compared, each against the cell's limit:

* ``loss_gap`` - the largest relative gap between the two losses over the
  steps;
* ``grad_gap`` - the first gradient as the optimizer gets it (the server
  estimate after step 1);
* ``update_gap`` - the parameters' change over the steps;
* ``msg_gap`` - the server estimate's change from step 1 to the last step,
  the sum of the aggregated EF messages of the later steps.

The last three are taken leaf by leaf and by the worst leaf: the gap
between the two norms, |a - b|, over the reference's norm of that leaf or
of the median leaf, whichever is larger. Leaves whose reference gradient
is under a thousandth of the median leaf's are left out of all three.

A fifth number checks the EF round element by element, where the model's
own rounding cannot hide what the round does to its state:

* ``round_mismatch`` - on whole selection blocks drawn from the seed, the
  reference's round (bench/reference/ef21.py ``round_blocks``) repeated on
  each step's own inputs in the program's state (the clients' v' after the
  step, their g and the server estimate before it): the share of the
  elements that either side changes where the program's g' or server
  estimate differs from the reference's by more than rounding
  (``ROUND_TOL`` of the magnitudes involved), by the worst leaf.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "msg_gap",
           "round_mismatch")
TINY_GRAD = 1e-3
# eight float32 ulps: a fused multiply-add or another order of the clients'
# sum differs from the reference by one or two
ROUND_TOL = 2.0 ** -20


def leaf_names(tree) -> List[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


def norms(tree) -> Dict[str, float]:
    return dict(zip(leaf_names(tree),
                    (float(x) for x in jax.device_get(_norms(tree)))))


def diff_norms(a, b) -> Dict[str, float]:
    return dict(zip(leaf_names(a),
                    (float(x) for x in jax.device_get(_diff_norms(a, b)))))


def worst_leaf(got: Dict[str, float], ref: Dict[str, float], keep):
    """(gap, leaf) of the worst kept leaf."""
    med = statistics.median(ref[k] for k in keep)
    gaps = {k: abs(got[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keep}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def round_mismatch(rounds: list, sample: list, ef: dict) -> dict:
    """``rounds``: the sampled state before the first step and after each
    (``program.first_steps``); ``sample``: ``program.round_sample``.
    Returns the number, its worst leaf, and its resolution (one element of
    the leaf with the fewest changed elements)."""
    from bench.reference import ef21

    worst, leaf, fewest = 0.0, None, None
    for j, s in enumerate(sample):
        b = s["block"]
        kb = max(1, min(b, int(round(ef["ratio"] * b))))
        inside = s["inside"]

        def blocks(x):
            x = np.where(inside, np.asarray(x, np.float32), np.float32(0))
            return x.reshape(x.shape[:-1] + (-1, b))
        bad = moved = 0
        for before, after in zip(rounds[:-1], rounds[1:]):
            g0, s0 = blocks(before["g"][j]), blocks(before["server"][j])
            g_ref, s_ref, c, mean = ef21.round_blocks(
                blocks(after["v"][j]), g0, s0, kb, ef["bits"])
            for got, want, was, msg in ((blocks(after["g"][j]), g_ref, g0, c),
                                        (blocks(after["server"][j]), s_ref,
                                         s0, mean)):
                changed = (msg != 0) | (got != was)
                off = ~(np.abs(got - want)
                        <= ROUND_TOL * (np.abs(was) + np.abs(msg)))
                bad += int(np.sum(off & changed))
                moved += int(np.sum(changed))
        share = bad / max(moved, 1)
        if leaf is None or share > worst:
            worst, leaf = share, s["leaf"]
        if moved and (fewest is None or moved < fewest):
            fewest = moved
    return {"round_mismatch": worst, "round_mismatch_leaf": leaf,
            "round_resolution": 1.0 / fewest if fewest else None}


def compare(got: dict, ref: dict, ef: dict = None) -> dict:
    """Each side: {"losses": [...], "grad": {leaf: norm}, "update": {...},
    "msg": {...}}; the program's side may add ``rounds`` and ``sample``
    (``program.first_steps``), which ``ef`` (the configuration's EF
    settings) turns into ``round_mismatch``. Returns {number: value} plus
    the worst leaves and the leaves left out."""
    med = statistics.median(ref["grad"].values())
    keep = [k for k, v in ref["grad"].items() if v >= TINY_GRAD * med]
    out = {"loss_gap": max(abs(a - b) / abs(b) if math.isfinite(a)
                           else math.inf
                           for a, b in zip(got["losses"], ref["losses"]))}
    for key in ("grad", "update", "msg"):
        if any(not math.isfinite(got[key][k]) for k in keep):
            out[f"{key}_gap"], out[f"{key}_leaf"] = math.inf, "non-finite"
            continue
        out[f"{key}_gap"], out[f"{key}_leaf"] = worst_leaf(
            got[key], ref[key], keep)
    out["left_out"] = sorted(set(ref["grad"]) - set(keep))
    if "rounds" in got:
        out.update(round_mismatch(got["rounds"], got["sample"], ef))
    return out


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every number is finite and within its limit."""
    return all(n in limits and limits[n] is not None
               and math.isfinite(numbers[n]) and numbers[n] <= limits[n]
               for n in NUMBERS)
