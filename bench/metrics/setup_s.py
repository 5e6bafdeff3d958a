"""Seconds from process start to the first timed step: imports, the
session and its jitted init, the step's compile (or cache load) and the
first two steps the comparison reads."""


def read(run):
    return run["setup_s"]
