"""Device time of the codec's decompress per allreduce call: ops whose
outermost codec scope is ``gz.decompress``, the union per chip averaged
over the chips, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.codec("gz.decompress"))
