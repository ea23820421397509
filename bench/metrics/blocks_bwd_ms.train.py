"""Device time of the layer stack's backward per training step, remat's
recompute included: ops under ``transpose(...(model.blocks))``, the union
per chip averaged over the chips, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.under("model.blocks", backward=True))
