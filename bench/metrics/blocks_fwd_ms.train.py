"""Device time of the layer stack's forward per training step: ops under
``model.blocks`` with no ``transpose`` at or outside it, the union per
chip averaged over the chips, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.under("model.blocks", backward=False))
