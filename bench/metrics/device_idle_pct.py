"""Share of the traced window in which no operation ran on a chip,
as a mean over the cell's chips."""
from bench import trace as tr


def read(ctx):
    lo, hi = ctx["window"]
    devs = ctx["trace_devices"]
    busy = sum(tr.busy(d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
