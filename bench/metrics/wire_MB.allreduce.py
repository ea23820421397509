"""Bytes one rank ships per allreduce call, as the program counts them
(``CollectiveResult.wire_bytes``), in MB."""


def read(run):
    wire = run.counters.get("wire_bytes")
    return None if wire is None else wire / 1e6
