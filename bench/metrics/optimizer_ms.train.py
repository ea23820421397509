"""Device time of the update per training step: ops under
``train.optimizer`` (global gradient norm, AdamW, skip merge), the union
per chip averaged over the chips, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.under("train.optimizer"))
