"""Share of the traced window in which a chip sat idle between one
execution of the step's program and the next (the host's data,
dispatch and wait between steps), as a mean over the cell's chips."""
from bench import trace as tr


def read(ctx):
    lo, hi = ctx["window"]
    devs = ctx["trace_devices"]
    if not any(d.modules for d in devs):
        return None
    gap = sum(tr.step_gaps(d, lo, hi) for d in devs) / len(devs)
    return 100.0 * gap / (hi - lo)
