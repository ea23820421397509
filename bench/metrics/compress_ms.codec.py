"""Device time of compress per round trip: ops whose outermost codec
scope is ``gz.compress``, in milliseconds."""
from bench import scopes


def read(run):
    return scopes.ms_per_call(run, scopes.codec("gz.compress"))
