"""Device time of collective operations per allreduce call, on the
busiest chip, in milliseconds."""
from bench import trace


def read(run):
    t, c = run.trace, run.counters
    if not c.get("calls"):
        return None
    worst = max(t.busy_s_in(d, (trace.COLLECTIVE,)) for d in range(t.n_devices))
    if worst <= 0:
        return None
    return 1e3 * worst / c["calls"]
