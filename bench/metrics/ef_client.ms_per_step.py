"""Device self time per step of the clients' side of the EF round: the ops
under the program's ``ef_client`` scope (bench/scopes.py) - the EF
update, compression and wire encode, with the uplink kernel's launches
and their pads."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, "ef_client")
