"""Algorithm bandwidth of the EF aggregation's collectives: one step's
operand bytes per chip (the program's collective counter) over their
device time per step (``ef_collective.ms_per_step``), in GB/s. A ring
all-reduce over n chips puts 2(n-1)/n times these bytes on each chip's
links. None for a program without the counter."""
from bench import collectives


def read(run):
    n = collectives.bytes_per_step()
    ms = collectives.ms_per_step(run)
    if n is None or not ms:
        return None
    return n / (ms * 1e-3) / 1e9
