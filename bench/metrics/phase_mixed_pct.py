"""Share of the traced window spent in operations whose instructions
compute two or more phases (fusions across scopes), as a mean over the
cell's chips: how far the single-phase shares can be off."""
from bench import phases


def read(ctx):
    s = phases.shares(ctx)
    return None if s is None else s["mixed"]
