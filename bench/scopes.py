"""Device time per named scope of the program.

The program names its layers with ``jax.named_scope`` (``gz.compress``,
``gz.hop``, ``gz.decompress``, ``model.blocks``, ``model.head``,
``train.optimizer``, ``train.grad_sync``). A scope reaches the compiled HLO
as each instruction's ``metadata={op_name=...}``, a path such as
``jit(step)/transpose(jvp(model.blocks))/while/body/dot_general``: a
transform wraps the scope it applies to, so backward ops sit under
``transpose(jvp(<scope>))``.

A TPU profile carries that op_name in the metadata of each device op's
event, as its ``tf_op`` stat (``<op_name>:<op_type>``). The reduced trace a
reader is given keeps only instruction names, and ``jax.profiler``'s
``ProfileData`` shows an event's own stats but not its metadata's, so the
profile file is read again here, with a minimal schema of the XSpace
protocol buffer: the newest ``.xplane.pb`` under ``bench/.traces`` whose
``window`` span is the run's. Each op takes the op_name of its own event,
so two programs of one cell that share an instruction name keep apart.

A scope's device seconds are, on each chip, the union of the intervals of
the ops in it (a ``while`` and the kernels nested in it count once),
averaged over the chips. The scope names are spelled out in the readers,
not imported from the program: a profile without them (a program without
scopes, or a CPU client, whose events carry no ``tf_op``) selects no op,
and the readers then report nothing.
"""
from __future__ import annotations

import functools
import pathlib
import re

import numpy as np

from bench import trace

CODEC = ("gz.compress", "gz.hop", "gz.decompress")
TRACES = pathlib.Path(__file__).resolve().parent / ".traces"
WINDOW_SLACK_NS = 1000  # ProfileData's float ns against the file's ints
_WRAPPED_RE = re.compile(r"^([\w\-]+)\((.*)\)$")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")


def path(op_name: str) -> list:
    """``[(scope, transforms)]`` for each component of an op_name:
    ``transpose(jvp(model.blocks))`` is ``("model.blocks", ("transpose",
    "jvp"))``."""
    out = []
    for part in op_name.split("/"):
        transforms = []
        while (m := _WRAPPED_RE.match(part)):
            transforms.append(m.group(1))
            part = m.group(2)
        out.append((part, tuple(transforms)))
    return out


def outermost(op_name: str, scopes) -> str | None:
    """The first of ``scopes`` on the op's path, or None."""
    for scope, _ in path(op_name):
        if scope in scopes:
            return scope
    return None


def codec(scope: str):
    """Selects the ops whose outermost codec scope is ``scope`` (a two-pass
    hop's compress is hop time)."""
    return lambda op_name: outermost(op_name, CODEC) == scope


def under(scope: str, backward: bool | None = None):
    """Selects the ops under ``scope``; with ``backward`` True (False),
    only those under (not under) a ``transpose`` at or outside it."""
    def select(op_name: str) -> bool:
        transposed = False
        for name, transforms in path(op_name):
            transposed = transposed or "transpose" in transforms
            if name == scope:
                return backward is None or backward == transposed
        return False

    return select


@functools.cache
def _xspace():
    """The message class of a minimal XSpace: the fields read here, with
    the field numbers of the profiler's ``xplane.proto``; names are bytes so
    that nothing is decoded that is not read."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")
    schema = {
        "XStat": [("metadata_id", 1, F.TYPE_INT64), ("str_value", 5, F.TYPE_BYTES),
                  ("ref_value", 7, F.TYPE_UINT64)],
        "XStatMetadata": [("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_BYTES)],
        "XEventMetadata": [("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_BYTES),
                           ("stats", 5, "XStat")],
        "XEvent": [("metadata_id", 1, F.TYPE_INT64), ("offset_ps", 2, F.TYPE_INT64),
                   ("duration_ps", 3, F.TYPE_INT64)],
        "XLine": [("name", 2, F.TYPE_BYTES), ("timestamp_ns", 3, F.TYPE_INT64),
                  ("events", 4, "XEvent")],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64), ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, F.TYPE_BYTES), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"),
                   ("stat_metadata", 5, "StatMetadataEntry")],
        "XSpace": [("planes", 1, "XPlane")],
    }
    for name, fields in schema.items():
        m = f.message_type.add(name=name)
        for fname, number, ftype in fields:
            if isinstance(ftype, str):  # a message: repeated, or a map's value
                label = F.LABEL_OPTIONAL if fname == "value" else F.LABEL_REPEATED
                m.field.add(name=fname, number=number, label=label,
                            type=F.TYPE_MESSAGE, type_name=f".bench_xspace.{ftype}")
            else:
                m.field.add(name=fname, number=number, label=F.LABEL_OPTIONAL,
                            type=ftype)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def _window_ps(space):
    """The longest ``window`` span of the host planes, in ps, or None."""
    best = None
    for plane in space.planes:
        if not plane.name.startswith(b"/host:"):
            continue
        ids = {e.key for e in plane.event_metadata if e.value.name == b"window"}
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                if ev.metadata_id in ids:
                    w = (base + ev.offset_ps, base + ev.offset_ps + ev.duration_ps)
                    if best is None or w[1] - w[0] > best[1] - best[0]:
                        best = w
    return best


def _op_names(plane) -> dict:
    """Event metadata id -> op_name, from each op's ``tf_op`` stat."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    out = {}
    for e in plane.event_metadata:
        for st in e.value.stats:
            if stat_names.get(st.metadata_id) != b"tf_op":
                continue
            raw = st.str_value or stat_names.get(st.ref_value, b"")
            op_name = raw.decode(errors="replace").rpartition(":")[0]
            if op_name:
                out[e.key] = op_name
    return out


@functools.lru_cache(maxsize=2)
def _scoped(file: str, stamp: tuple, n_devices: int):
    """``(window_ps, {device: (op_names, ids, starts, ends)})`` of one
    profile file (``stamp`` is its mtime and size): per device, event
    metadata id -> op_name, and the metadata id and interval of each device
    op that carries an op_name, clipped to the window, in ps from its
    start (a chip's clock in ps does not fit 64 bits)."""
    space = _xspace()()
    space.ParseFromString(pathlib.Path(file).read_bytes())
    window = _window_ps(space)
    out = {}
    if window is None:
        return None, out
    lo, hi = window
    lines = {name.encode() for name in trace.DEVICE_LINES}
    for plane in space.planes:
        m = _DEVICE_RE.match(plane.name.decode(errors="replace"))
        if not m or int(m.group(1)) >= n_devices:
            continue
        names = _op_names(plane)
        ids, starts, durations = [], [], []
        for line in plane.lines:
            if line.name not in lines:
                continue
            base = line.timestamp_ns * 1000 - lo
            for ev in line.events:
                ids.append(ev.metadata_id)
                starts.append(base + ev.offset_ps)
                durations.append(ev.duration_ps)
        ids = np.array(ids, dtype=np.int64)
        starts = np.array(starts, dtype=np.int64)
        ends = np.minimum(starts + np.array(durations, dtype=np.int64), hi - lo)
        starts = np.maximum(starts, 0)
        keep = np.isin(ids, np.fromiter(names, dtype=np.int64)) & (ends > starts)
        out[int(m.group(1))] = (names, ids[keep], starts[keep], ends[keep])
    return window, out


def _union_ps(starts, ends) -> int:
    """Length of the union of intervals: sorted by start, each adds what
    reaches past the furthest end before it."""
    if not len(starts):
        return 0
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    reach = np.concatenate((starts[:1], np.maximum.accumulate(ends)[:-1]))
    return int(np.maximum(ends - np.maximum(starts, reach), 0).sum())


def scoped_ops(run) -> dict:
    """The per-device ops of ``_scoped`` for the run's profile: the newest
    file under ``TRACES`` whose window is the run's; empty where there is
    none."""
    lo, hi = run.trace.window
    files = sorted(TRACES.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for p in files:
        st = p.stat()
        window, ops = _scoped(str(p), (st.st_mtime_ns, st.st_size),
                              run.trace.n_devices)
        if window is not None and (
                abs(window[0] / 1000 - lo) <= WINDOW_SLACK_NS
                and abs(window[1] / 1000 - hi) <= WINDOW_SLACK_NS):
            return ops
    return {}


def seconds(run, select) -> float | None:
    """Device seconds of the traced ops whose op_name ``select`` accepts,
    the union per chip averaged over the chips; None where no op is
    selected."""
    total, seen = 0, False
    for names, ids, starts, ends in scoped_ops(run).values():
        chosen = [i for i, op_name in names.items() if select(op_name)]
        mask = np.isin(ids, np.array(chosen, dtype=np.int64))
        seen = seen or bool(mask.any())
        total += _union_ps(starts[mask], ends[mask])
    if not seen:
        return None
    return total * 1e-12 / max(run.trace.n_devices, 1)


def ms_per_call(run, select) -> float | None:
    """``seconds`` per timed call (a call or a training step), in ms."""
    s, calls = seconds(run, select), run.counters.get("calls")
    return None if s is None or not calls else 1e3 * s / calls
