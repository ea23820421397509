"""Device time of decompress per round trip: ops whose outermost codec
scope is ``gz.decompress``, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.codec("gz.decompress"))
