"""Share of the step's device time that none of the program's scopes
claims (bench/scopes.py): how much of the step the per-layer times cover.
None when the compiled step carries none of the scopes."""
from bench import scopes


def read(run):
    t = scopes.step_times(run)
    if t is None or not t["present"]:
        return None
    total = sum(t["scopes"].values()) + t["unscoped"]
    return 100.0 * t["unscoped"] / total if total > 0 else None
