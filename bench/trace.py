"""Reduction of a profiler trace (``.xplane.pb``) to busy time, op classes
and idle gaps.

Device operations are the events of each TPU plane's "XLA Ops" and "Async
XLA Ops" lines, named there by their HLO line (on a CPU, the host events
that carry an ``hlo_op`` stat, by their ``device_ordinal``). Busy time is
the union of their intervals inside the benchmark's ``window`` span. Each
operation is put in one class:

* ``custom-call``: a Pallas kernel (a custom call to ``tpu_custom_call``;
  XLA's own custom calls are ``other``);
* ``collective``: collective-permute, all-reduce, all-gather,
  reduce-scatter, all-to-all, send and receive;
* ``other``: fusions and everything else.

The class comes from the compiled HLO text of the programs the window
runs (``categories_from_hlo``), by instruction name, and else from the
op's own name. The host's spans (``dispatch``, ``block``, ``data``,
``host``) label the device's idle gaps by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

COLLECTIVE = "collective"
CUSTOM = "custom-call"
OTHER = "other"
HOST_SPANS = ("dispatch", "block", "data", "host")
DEVICE_LINES = ("XLA Ops", "Async XLA Ops")
_EVENT_NAME_RE = re.compile(r"^%?([\w.\-]+) = ")
_COLLECTIVE_RE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|\bsend\b|\brecv\b|send-done|recv-done")
_CUSTOM_RE = re.compile(r"tpu_custom_call")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


def classify_text(text: str) -> str:
    if _COLLECTIVE_RE.search(text):
        return COLLECTIVE
    if _CUSTOM_RE.search(text):
        return CUSTOM
    return OTHER


def categories_from_hlo(hlo_text: str) -> dict:
    """Instruction name -> class, from compiled HLO text. A fusion takes
    the class of what it calls only through its own line (a collective
    or custom call fused away keeps its opcode on that line)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        # the opcode is the word right before the operand list
        op = re.search(r"\s([a-z][\w\-]*)\(", " " + rhs.split(", metadata=")[0])
        opcode = op.group(1) if op else ""
        if opcode == "custom-call":
            # Pallas kernels; XLA's own custom calls (AllocateBuffer,
            # ConcatBitcast, ...) are not kernels of the program
            pallas = 'custom_call_target="tpu_custom_call"' in rhs
            out[name] = CUSTOM if pallas else OTHER
        elif _COLLECTIVE_RE.search(opcode):
            out[name] = COLLECTIVE
        else:
            out[name] = OTHER
    return out


@dataclasses.dataclass
class Op:
    device: int
    name: str
    start: int  # ns
    end: int    # ns
    category: str


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Reduced:
    """A trace reduced to what the per-layer readers need."""

    n_devices: int
    window: tuple            # (start_ns, end_ns)
    ops: list                # Op inside the window
    host_spans: list         # (name, start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: int):
        return _union([(o.start, o.end) for o in self.ops
                       if o.device == device])

    def busy_s_of(self, device: int) -> float:
        return _length(self.busy_intervals(device)) * 1e-9

    def busy_s_in(self, device: int, categories) -> float:
        """Busy seconds on ``device`` in ops of the given classes."""
        return _length(_union([(o.start, o.end) for o in self.ops
                               if o.device == device
                               and o.category in categories])) * 1e-9

    def busy_s_outside(self, device: int, categories) -> float:
        """Busy seconds on ``device`` in ops of none of the given classes."""
        return _length(_union([(o.start, o.end) for o in self.ops
                               if o.device == device
                               and o.category not in categories])) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(self.busy_s_of(d) for d in range(self.n_devices)) / max(
            self.n_devices, 1)

    def idle_share(self) -> float:
        """1 - busy / window, averaged over the devices (0..1)."""
        if self.window_s <= 0:
            return float("nan")
        return 1.0 - self.busy_s / self.window_s

    def gaps(self, device: int):
        """Idle intervals of one device inside the window."""
        lo, hi = self.window
        busy = self.busy_intervals(device)
        out, cur = [], lo
        for s, e in busy:
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            out.append((cur, hi))
        return out

    def gap_label(self, gap) -> str:
        s, e = gap
        best, label = 0, "other"
        for name, hs, he in self.host_spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, name
        return label

    def breakdown(self, top: int = 10) -> dict:
        """Device ops by total seconds (mean over devices) and idle time by
        the host span it fell in (mean over devices)."""
        n = max(self.n_devices, 1)
        per_op: dict = {}
        for o in self.ops:
            key = f"{o.category}:{o.name}"
            per_op[key] = per_op.get(key, 0) + (o.end - o.start)
        idle: dict = {}
        for d in range(self.n_devices):
            for g in self.gaps(d):
                lab = self.gap_label(g)
                idle[lab] = idle.get(lab, 0) + (g[1] - g[0])
        rank = lambda t: sorted(t.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v * 1e-9 / n] for k, v in rank(per_op)],
                "idle_gaps": [[k, v * 1e-9 / n] for k, v in rank(idle)]}


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _device_index(plane_name: str):
    m = re.match(r"/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def reduce_profile(profile, *, n_devices: int, op_categories: dict) -> Reduced:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    raw_ops, cpu_ops, host_spans, windows = [], [], [], []
    tpu_seen = False
    for plane in profile.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            tpu_seen = True
            if dev >= n_devices:
                continue
            for line in plane.lines:
                if line.name not in DEVICE_LINES:
                    continue
                for ev in line.events:
                    raw_ops.append((dev, ev.name, int(ev.start_ns),
                                    int(ev.end_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window":
                        windows.append((int(ev.start_ns), int(ev.end_ns)))
                    elif ev.name in HOST_SPANS:
                        host_spans.append((ev.name, int(ev.start_ns),
                                           int(ev.end_ns)))
                    elif plane.name == "/host:CPU":
                        hlo = _stat(ev, "hlo_op")
                        if hlo is not None:
                            d = _stat(ev, "device_ordinal")
                            cpu_ops.append((int(d or 0), str(hlo),
                                            int(ev.start_ns), int(ev.end_ns)))
    if not tpu_seen:  # a CPU client: its ops run on host threads
        raw_ops = cpu_ops
    if not windows:
        raise ValueError("no 'window' span in the trace")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    ops = []
    for dev, text, s, e in raw_ops:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 <= s2 or dev >= n_devices:
            continue
        m = _EVENT_NAME_RE.match(text)  # a TPU op's name is its HLO line
        name = m.group(1) if m else text
        cat = op_categories.get(name) or classify_text(text)
        ops.append(Op(dev, name, s2, e2, cat))
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in host_spans
             if min(e, hi) > max(s, lo)]
    return Reduced(n_devices=n_devices, window=(lo, hi), ops=ops,
                   host_spans=spans)


def newest_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir, *, n_devices: int, op_categories: dict) -> Reduced:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(newest_xplane(trace_dir)))
    return reduce_profile(profile, n_devices=n_devices,
                          op_categories=op_categories)
