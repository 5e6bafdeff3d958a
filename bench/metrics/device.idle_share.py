"""Share of the traced window in which a chip ran no operation, averaged
over the cell's chips: 1 - (union of the chip's op intervals) / window."""
from bench import trace as T


def read(run):
    table = run["trace"]
    if table is None or not table["devices"]:
        return None
    w = T.window(table)
    shares = [1.0 - T.busy(table, d, w) / (w[1] - w[0])
              for d in table["devices"]]
    return 100.0 * sum(shares) / len(shares)
