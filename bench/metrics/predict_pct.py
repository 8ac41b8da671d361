"""Share of the traced window spent in operations that compute the
SpecTrain weight prediction alone (the ``predict`` scope: Eq. 4 in
``core/spectrain.py``), as a mean over the cell's chips."""
from bench import phases


def read(ctx):
    s = phases.shares(ctx)
    return None if s is None else s["predict"]
