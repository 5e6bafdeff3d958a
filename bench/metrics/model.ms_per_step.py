"""Device self time per step of every client's forward and backward pass:
the ops under the program's ``model_fwd_bwd`` scope (bench/scopes.py),
remat recompute included."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, "model_fwd_bwd")
