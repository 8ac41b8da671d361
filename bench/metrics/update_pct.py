"""Share of the traced window spent in operations that compute the
optimizer update alone (the ``update`` scope: clipping and
``optim/sgd.py``), as a mean over the cell's chips."""
from bench import phases


def read(ctx):
    s = phases.shares(ctx)
    return None if s is None else s["update"]
