"""Tokens of every step completed in the untraced window, over the
window's seconds (host clock, from the first dispatch to the last step's
outputs being ready)."""


def read(run):
    w = run["window"]
    if run["trace"] is not None or w["seconds"] <= 0:
        return None
    return w["steps"] * run["tokens_per_step"] / w["seconds"]
