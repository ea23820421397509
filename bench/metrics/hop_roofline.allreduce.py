"""Codec work of one ring allreduce as a share of its HBM roofline.

The least time is the codec bytes the ring algorithm must move on one
rank (``bench/work.py: ring_allreduce_bytes``, from the stream size the
seed's data produced) at the chip's HBM bandwidth. It is divided by the
device time per call spent outside collective operations, averaged over
the chips.
"""
from bench import trace, work


def read(run):
    t, c = run.trace, run.counters
    if not c.get("calls"):
        return None
    busy = sum(t.busy_s_outside(d, (trace.COLLECTIVE,))
               for d in range(t.n_devices)) / t.n_devices / c["calls"]
    if busy <= 0:
        return None
    return 100.0 * work.least_seconds(c["least_bytes"], run.peaks) / busy
