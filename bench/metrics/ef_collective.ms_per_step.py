"""Device self time per step of the EF aggregation's cross-chip
collectives: the ops under the program's ``ef_collective`` scope
(bench/collectives.py), averaged over the chips. An async all-reduce shows
on a chip's XLA Ops line as its start and its done, so this is the
collective time the step waits for, not the time its bytes spend on the
links. Part of ``ef_aggregate.ms_per_step``."""
from bench import collectives


def read(run):
    return collectives.ms_per_step(run)
