"""Least bytes a codec operation has to move through HBM.

These counts are properties of the algorithm, not of its implementation:
a fused or re-split kernel changes the time a roofline share divides by,
never these numbers. A stream is its packed words plus the per-block
bitwidth and anchor (one int32 each per block of ``block`` elements).
"""
from __future__ import annotations

F32 = 4


def stream_bytes(nwords: float, n_elems: int, block: int = 256) -> float:
    """Bytes of one compressed stream of ``n_elems`` values."""
    n_blocks = -(-int(n_elems) // block)
    return 4.0 * nwords + 8.0 * n_blocks


def codec_roundtrip_bytes(n_elems: int, nwords: float, block: int = 256) -> float:
    """compress (read n f32, write the stream), then decompress (read the
    stream, write n f32)."""
    s = stream_bytes(nwords, n_elems, block)
    return 2.0 * (F32 * n_elems + s)


def ring_allreduce_bytes(n_elems: int, n_ranks: int, chunk_nwords: float,
                         block: int = 256) -> float:
    """Codec bytes one rank must move in a compressed ring allreduce of
    ``n_elems`` f32, with ``chunk_nwords`` words per chunk stream:

    * one compress of the rank's first chunk: read c f32, write a stream;
    * N-1 reduce hops, each reading a received stream and a local chunk
      and writing the next stream (the last one is the owner's reduced
      chunk, which the allgather forwards as it is);
    * the owner's reduced chunk written once as f32;
    * N-1 decompresses of the gathered streams into the output.
    """
    n = int(n_ranks)
    c = -(-int(n_elems) // n)
    s = stream_bytes(chunk_nwords, c, block)
    chunk = F32 * c
    return ((chunk + s) + (n - 1) * (2 * s + chunk) + chunk
            + (n - 1) * (s + chunk))


def least_seconds(nbytes: float, peaks: dict) -> float:
    return nbytes / peaks["hbm_bytes_per_s"]
