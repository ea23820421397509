"""Device time of the reduce hops per allreduce call: ops whose outermost
codec scope is ``gz.hop`` (fused, or decompress-reduce then compress), the
union per chip averaged over the chips, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.codec("gz.hop"))
