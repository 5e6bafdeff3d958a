"""Device self time per step of the optimizer: the ops under the program's
``optimizer`` scope (bench/scopes.py), its update and its apply."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, "optimizer")
