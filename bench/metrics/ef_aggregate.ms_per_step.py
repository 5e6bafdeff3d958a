"""Device self time per step of the EF round's aggregation: the ops under
the program's ``ef_aggregate`` scope (bench/scopes.py) - the decode,
the mean over clients (a collective across chips) and the server step."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, "ef_aggregate")
