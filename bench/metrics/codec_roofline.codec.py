"""Codec round trip as a share of its HBM roofline.

The least time is reading n f32 and writing the stream, then reading the
stream and writing n f32 (``bench/work.py: codec_roundtrip_bytes``, with
the stream size the seed's fields produced), at the chip's HBM bandwidth.
It is divided by the device busy time per round trip, all ops counted.
"""
from bench import work


def read(run):
    t, c = run.trace, run.counters
    if not c.get("calls"):
        return None
    busy = t.busy_s / c["calls"]
    if busy <= 0:
        return None
    return 100.0 * work.least_seconds(c["least_bytes"], run.peaks) / busy
