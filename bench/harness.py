"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run, with its
  plain reference beside it in ``bench/configs/<config>.ref.py``;
* ``bench/traffic/<traffic>.json``: the traffic mix; its ``driver`` key
  names the loop in ``bench/drivers/<driver>.py`` that runs it;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric, a
  function ``read(run)`` that returns a number or ``None``.

A driver module defines ``Driver(ctx)``. Its constructor does the whole
set-up (inputs, weights, compilation, warm-up) and fills
``setup_parts``; ``call(i)`` is one timed unit that ends in
``block_until_ready``; ``end_to_end(latencies, window_s)`` turns the
window into the cell's end-to-end metrics; ``counters()`` gives the
readers what the program counted; ``finish()`` frees the program's state,
runs the plain reference and returns the numbers compared.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
NOT_FINITE = 1e300  # what a NaN or infinite number compared is printed as


class BenchError(RuntimeError):
    """A run that cannot give a result: it prints none."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    checks: list   # Check
    failed: int    # answers of the window that the comparison refused


@dataclasses.dataclass
class Context:
    """What a driver is given: the cell, the run's seed and its devices."""

    cell: Cell
    seed: int
    devices: list
    root: pathlib.Path
    log: object = None  # print-like callable for the earlier lines

    def say(self, msg: str) -> None:
        (self.log or _say)(msg)


def _say(msg: str) -> None:
    print(msg, flush=True)


def load_spec(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def resolve_cell(spec: dict, name: str, root: pathlib.Path = ROOT,
                 traffic_overrides: dict | None = None,
                 config_overrides: dict | None = None) -> Cell:
    """The cell ``name`` with its files read. The overrides replace keys of
    the traffic and configuration (the tests run cells at small sizes)."""
    w = _by_name(spec["workloads"], name, "workload")
    c = _by_name(spec["configs"], w["config"], "configuration")
    config = json.loads((root / c["file"]).read_text())
    config.update(config_overrides or {})
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    traffic.update(traffic_overrides or {})
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in e2e_names]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: pathlib.Path, tag: str):
    """Import a file by its path (names hold dots, so no plain import)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    mod_name = f"_bench_{tag}_" + "".join(
        ch if ch.isalnum() else "_" for ch in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver_module(cell: Cell, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "drivers" / f"{cell.traffic['driver']}.py",
                       "driver")


def reference_module(cell: Cell, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "configs" / f"{cell.config_name}.ref.py",
                       "ref")


def metric_reader(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py", "metric").read


def load_peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


def import_program(root: pathlib.Path = ROOT) -> None:
    """Put the checkout's ``src`` first on the path and make sure the
    library comes from there."""
    src = root / "src"
    if not (src / "repro" / "core" / "comm.py").is_file():
        raise BenchError(f"no program sources under {src}")
    sys.path.insert(0, str(src))
    from repro.core import comm

    if src.resolve() not in pathlib.Path(comm.__file__).resolve().parents:
        raise BenchError(f"repro was imported from {comm.__file__}, not {src}")


class CompileCounter:
    """Counts JAX compile-pipeline events (tracing, lowering, backend
    compiles and persistent-cache lookups) from the moment it is armed."""

    def __init__(self):
        import jax

        self.events = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.armed and event.startswith("/jax/core/compile/"):
            self.events += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def timed_window(call, seconds: float):
    """Closed loop: call after call until ``seconds`` have passed. Returns
    the per-call latencies and the window, from the first call's start to
    the last call's end."""
    lat = []
    i = 0
    with annotate("window"):
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            call(i)
            t1 = time.perf_counter()
            lat.append(t1 - t)
            i += 1
            if t1 - t0 >= seconds:
                break
    return lat, t1 - t0


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


@dataclasses.dataclass
class TracedRun:
    """What a per-layer reader is given."""

    trace: object       # bench.trace.Reduced
    counters: dict      # the driver's counters plus calls and window_s
    peaks: dict
    chips: int


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, root: pathlib.Path = ROOT,
             log=None, driver_cls=None, peaks: dict | None = None) -> dict:
    """Set up, measure, check and reduce one run; returns the result
    object (the last line's keys, ``checks`` last)."""
    import jax

    from bench import trace as trace_mod

    counter = CompileCounter()
    ctx = Context(cell=cell, seed=seed, devices=list(devices), root=root,
                  log=log)
    kind = devices[0].device_kind
    if peaks is None:
        peaks = load_peaks(kind, root)
    t_imported = time.perf_counter()
    Driver = driver_cls or driver_module(cell, root).Driver
    drv = Driver(ctx)
    t_ready = time.perf_counter()
    parts = {"import": t_imported - t_start}
    parts.update(drv.setup_parts)
    ctx.say("setup: " + " ".join(f"{k}={v:.3f}s" for k, v in parts.items())
            + f" total={t_ready - t_start:.3f}s cache_hits={counter.cache_hits}"
            f" cache_misses={counter.cache_misses}")

    trace_dir = None
    counter.armed = True
    if trace:
        trace_dir = root / "bench" / ".traces" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)  # one trace per cell
        trace_dir.mkdir(parents=True, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python calls
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        lat, window_s = timed_window(drv.call, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    counter.armed = False
    if counter.events:
        ctx.say(f"WARNING: {counter.events} compile events inside the window")
    peak = memory_peak(devices)
    metrics = {}
    breakdown = None
    if trace:
        reduced = trace_mod.reduce_dir(
            trace_dir, n_devices=len(devices), op_categories=drv.op_categories())
        counters = dict(drv.counters())
        counters.update(calls=len(lat), window_s=window_s)
        run = TracedRun(trace=reduced, counters=counters, peaks=peaks,
                        chips=len(devices))
        for m in cell.per_layer:
            value = metric_reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        breakdown = reduced.breakdown()
        ctx.say(f"trace: busy_s={reduced.busy_s} window_s={reduced.window_s} "
                f"calls={len(lat)}")
    else:
        e2e = drv.end_to_end(lat, window_s)
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": t_ready - t_start, "unit": "s"}
            elif m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    ctx.say(f"window: calls={len(lat)} window_s={window_s:.6f} "
            f"compile_events={counter.events} memory_peak_bytes={peak}")
    outcome = drv.finish()
    checks = outcome.checks
    correct = (bool(checks) and all(c.ok for c in checks)
               and len(lat) > 0 and outcome.failed == 0)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
    result = {"correct": correct, "attempted": len(lat),
              "failed": outcome.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {
        c.name: {"value": c.value if math.isfinite(c.value) else NOT_FINITE,
                 "limit": c.limit}
        for c in checks}
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
