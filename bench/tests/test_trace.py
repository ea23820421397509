"""The trace reduction: busy union, idle share, op classes, breakdown."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, trace


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: int
    end_ns: int
    stats: tuple = ()


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _profile():
    """Two TPU devices and a host thread, by hand (times in ns)."""
    host = Plane("/host:CPU", [Line("python", [
        Ev("window", 0, 1000),
        Ev("dispatch", 0, 100), Ev("block", 100, 600), Ev("data", 600, 1000)])])
    d0 = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step", 100, 900)]),
        Line("XLA Ops", [Ev("fusion.1", 100, 300), Ev("fusion.2", 250, 400),
                         Ev("tpu_custom_call.3", 400, 500),
                         Ev("collective-permute-done.4", 700, 800)])])
    # a TPU names each op by its HLO line; async ops have a line of their own
    d1 = Plane("/device:TPU:1", [
        Line("XLA Ops", [Ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), "
                            "kind=kLoop", 200, 500)]),
        Line("Async XLA Ops", [Ev("%copy-start = (u32[8], u32[8]) "
                                  "copy-start(u32[8] %c)", 450, 600)])])
    d2 = Plane("/device:TPU:2", [Line("XLA Ops", [Ev("fusion.1", 0, 1000)])])
    return type("P", (), {"planes": [host, d0, d1, d2]})()


def test_busy_union_idle_share_and_classes():
    r = trace.reduce_profile(_profile(), n_devices=2, op_categories={})
    assert r.window == (0, 1000)
    # device 0: [100, 500] and [700, 800]; device 1: [200, 600]
    assert r.busy_s_of(0) == pytest.approx(500e-9)
    assert r.busy_s_of(1) == pytest.approx(400e-9)
    assert r.busy_s == pytest.approx(450e-9)
    assert r.idle_share() == pytest.approx(0.55)
    assert r.busy_s_in(0, (trace.COLLECTIVE,)) == pytest.approx(100e-9)
    assert r.busy_s_in(0, (trace.CUSTOM,)) == pytest.approx(100e-9)
    assert r.busy_s_outside(0, (trace.COLLECTIVE,)) == pytest.approx(400e-9)
    assert {o.device for o in r.ops} == {0, 1}  # device 2 is not the cell's


def test_gaps_take_the_host_span_they_fall_in():
    r = trace.reduce_profile(_profile(), n_devices=2, op_categories={})
    assert r.gaps(0) == [(0, 100), (500, 700), (800, 1000)]
    assert [r.gap_label(g) for g in r.gaps(0)] == ["dispatch", "block", "data"]
    b = r.breakdown()
    idle = dict(b["idle_gaps"])
    # device 0 idles 100 ns in dispatch, 200 in block (a tie with data
    # goes to the span that came first), 200 in data; device 1 idles
    # 200 in dispatch (a tie with block) and 400 in data; means over 2
    assert idle["data"] == pytest.approx((200 + 400) / 2 * 1e-9)
    assert idle["dispatch"] == pytest.approx((100 + 200) / 2 * 1e-9)
    assert idle["block"] == pytest.approx(200 / 2 * 1e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    ops = dict(b["device_ops"])
    assert ops["other:fusion.1"] == pytest.approx((200 + 300) / 2 * 1e-9)
    assert ops["other:copy-start"] == pytest.approx(150 / 2 * 1e-9)


def test_classes_from_hlo_text():
    hlo = """
    %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c
    custom-call.2 = (u32[16]{0}, s32[2]{0}) custom-call(f32[8]{0} %x), custom_call_target="tpu_custom_call"
    ROOT %collective-permute-start.1 = (f32[8], f32[8]) collective-permute-start(f32[8]{0} %y), source_target_pairs={{0,1}}
    all-reduce.3 = f32[] all-reduce(f32[] %z), to_apply=%add
    custom-call.9 = f32[8]{0} custom-call(f32[8]{0} %w), custom_call_target="AllocateBuffer"
    """
    cats = trace.categories_from_hlo(hlo)
    assert cats["fusion.7"] == trace.OTHER
    assert cats["custom-call.2"] == trace.CUSTOM
    assert cats["collective-permute-start.1"] == trace.COLLECTIVE
    assert cats["all-reduce.3"] == trace.COLLECTIVE
    assert cats["custom-call.9"] == trace.OTHER
    assert trace.classify_text("all-gather-start") == trace.COLLECTIVE


def test_reduces_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x.T + 1.0)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    lowered = f.lower(x).compile()

    def call(i):
        with harness.annotate("dispatch"):
            y = lowered(x)
        with harness.annotate("block"):
            y.block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    try:
        lat, window = harness.timed_window(call, 0.2)
    finally:
        jax.profiler.stop_trace()
    r = trace.reduce_dir(tmp_path, n_devices=1,
                         op_categories=trace.categories_from_hlo(lowered.as_text()))
    assert r.ops, "no device op found in the CPU trace"
    assert 0 < r.busy_s <= r.window_s
    assert 0.0 <= r.idle_share() < 1.0
    assert r.window_s == pytest.approx(window, rel=0.05)
    assert {o.category for o in r.ops} <= {trace.OTHER, trace.CUSTOM,
                                          trace.COLLECTIVE}
    labels = {name for name, _ in r.breakdown()["idle_gaps"]}
    assert labels <= {"dispatch", "block", "other"}
