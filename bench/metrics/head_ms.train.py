"""Device time of the head per training step, forward and backward: ops
under ``model.head`` (final norm, LM head, cross-entropy), the union per
chip averaged over the chips, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.under("model.head"))
