"""Share of the traced window spent in operations that compute the
head alone (the ``head`` scope: ``Model.head_loss`` and its vjp), as a
mean over the cell's chips."""
from bench import phases


def read(ctx):
    s = phases.shares(ctx)
    return None if s is None else s["head"]
