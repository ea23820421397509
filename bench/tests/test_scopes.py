"""Device time per named scope, read from a profile file: the op_name of
each op's event metadata, the outermost codec scope, the transpose split,
the union per chip, names that two programs share, and the choice of the
run's own file."""
import re

import pytest
from jax.profiler import ProfileData

from bench import harness, scopes, trace

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
T0 = 10**15  # ns; float ns are exact here

ALLREDUCE_HLO = """
%while.64 = (s32[], f32[8]) while((s32[], f32[8]) %t), condition=%c, body=%b, metadata={op_name="jit(body)/shmap_body/while" stack_frame_id=2}
%quantize_pack.2 = (u32[8]{0}) custom-call(f32[8]{0} %x), custom_call_target="tpu_custom_call", metadata={op_type="pallas_call" op_name="jit(body)/shmap_body/while/body/gz.compress/pallas_call" source_file="c.py" source_line=3}
%unpack_reduce_repack.8 = (u32[8]{0}) custom-call(u32[8]{0} %p, f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shmap_body/while/body/gz.hop/pallas_call"}
%quantize_pack.5 = (u32[8]{0}) custom-call(f32[8]{0} %u), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shmap_body/while/body/gz.hop/gz.compress/pallas_call"}
ROOT %unpack_dequantize.9 = f32[8]{0} custom-call(u32[8]{0} %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/shmap_body/gz.decompress/pallas_call"}
%collective-permute-start.7 = (f32[8], f32[8]) collective-permute-start(f32[8]{0} %y), source_target_pairs={{0,1}}, metadata={op_name="jit(body)/shmap_body/while/body/ppermute"}
%copy.3 = f32[8]{0} copy(f32[8]{0} %z)
"""

TRAIN_HLO = """
%while.145 = (s32[]) while((s32[]) %t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp(model.blocks)/while"}
%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput, calls=%f3, metadata={op_name="jit(step)/jvp(model.blocks)/while/body/closed_call/dot_general"}
%while.148 = (s32[]) while((s32[]) %t), condition=%c, body=%b, metadata={op_name="jit(step)/transpose(jvp(model.blocks))/while"}
%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f9, metadata={op_name="jit(step)/transpose(jvp(model.blocks))/while/body/closed_call/checkpoint/rematted_computation/mul"}
%fusion.11 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f11, metadata={op_name="jit(step)/jvp(model.head)/dot_general"}
%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f12, metadata={op_name="jit(step)/transpose(jvp(model.head))/dot_general"}
%fusion.20 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f20, metadata={op_name="jit(step)/train.optimizer/mul"}
%copy-start.1 = (f32[8], f32[8]) copy-start(f32[8]{0} %p), metadata={op_name="jit(step)/embed"}
"""


def op_names(hlo: str) -> dict:
    """Instruction name -> op_name, as a TPU profile's ``tf_op`` carries
    it for each op."""
    return dict(re.findall(r'%([\w.\-]+) = .*op_name="([^"]*)"', hlo))


def xspace(events_by_device, *, t0=T0, window=(0, 1000), by_ref=False):
    """A serialized XSpace as a TPU profile lays it out: a host ``window``
    span, and per device an "XLA Ops" line whose events are
    ``(instruction, start_ns, end_ns, op_name or None)``, each with its own
    event metadata holding the HLO line and the op_name as ``tf_op``
    (a string, or with ``by_ref`` a reference to a stat metadata's name)."""
    host = (f'planes {{ id: 1 name: "/host:CPU" '
            f'lines {{ id: 1 name: "python" timestamp_ns: {t0} '
            f'events {{ metadata_id: 1 offset_ps: {window[0] * 1000} '
            f'duration_ps: {(window[1] - window[0]) * 1000} }} }} '
            f'event_metadata {{ key: 1 value {{ id: 1 name: "window" }} }} }}')
    planes = [host]
    for d, evs in sorted(events_by_device.items()):
        body = [f'stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}']
        line = []
        for i, (name, s, e, op_name) in enumerate(evs, start=10):
            stats = ""
            if op_name is not None:
                if by_ref:
                    body.append(f'stat_metadata {{ key: {i} value {{ id: {i} '
                                f'name: "{op_name}:" }} }}')
                    stats = f'stats {{ metadata_id: 1 ref_value: {i} }}'
                else:
                    stats = f'stats {{ metadata_id: 1 str_value: "{op_name}:" }}'
            body.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "%{name} = f32[8]{{0}} op()" '
                        f'display_name: "{name}" {stats} }} }}')
            line.append(f'events {{ metadata_id: {i} offset_ps: {s * 1000} '
                        f'duration_ps: {(e - s) * 1000} }}')
        planes.append(f'planes {{ id: {d + 2} name: "/device:TPU:{d}" '
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: {t0} '
                      + " ".join(line) + " } " + " ".join(body) + " }")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


@pytest.fixture
def traces(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACES", tmp_path)
    return tmp_path


def write(traces, cell, blob):
    d = traces / cell
    d.mkdir(parents=True, exist_ok=True)
    (d / "p.xplane.pb").write_bytes(blob)
    return d


def traced_run(traces, events_by_device, counters, hlo, **kw):
    """A traced run over ``events_by_device`` ({device: [(name, s, e)]}),
    window [0, 1000] ns, op_names and classes from ``hlo``; ``hlo=None``
    leaves every op without an op_name."""
    names = op_names(hlo or "")
    events = {d: [(n, s, e, names.get(n) if hlo else None) for n, s, e in evs]
              for d, evs in events_by_device.items()}
    d = write(traces, "cell", xspace(events, **kw))
    reduced = trace.reduce_dir(d, n_devices=len(events_by_device),
                               op_categories=trace.categories_from_hlo(hlo or ""))
    return harness.TracedRun(trace=reduced, counters=counters, peaks=PEAKS,
                             chips=len(events_by_device))


ALLREDUCE_EVENTS = {
    0: [("while.64", 0, 900), ("quantize_pack.2", 100, 300),
        ("unpack_reduce_repack.8", 300, 600), ("quantize_pack.5", 600, 700),
        ("collective-permute-start.7", 700, 750),
        ("unpack_dequantize.9", 750, 950), ("copy.3", 950, 1000)],
    1: [("while.64", 0, 800), ("quantize_pack.2", 100, 200),
        ("unpack_reduce_repack.8", 200, 500), ("quantize_pack.5", 500, 600),
        ("unpack_dequantize.9", 800, 900)],
}
ALLREDUCE_COUNTERS = {"calls": 2, "window_s": 1e-6, "wire_bytes": 123.0,
                      "least_bytes": 4096.0}


def test_path_unwraps_transforms():
    assert scopes.path("jit(step)/transpose(jvp(model.blocks))/while") == [
        ("step", ("jit",)), ("model.blocks", ("transpose", "jvp")),
        ("while", ())]


@pytest.mark.parametrize("by_ref", [False, True])
def test_outermost_codec_scope_takes_the_op(traces, by_ref):
    run = traced_run(traces, ALLREDUCE_EVENTS, ALLREDUCE_COUNTERS,
                     ALLREDUCE_HLO, by_ref=by_ref)
    read = lambda name: harness.metric_reader(name)(run)
    # device 0: compress [100, 300]; hop [300, 700] (the two-pass hop's
    # compress is hop time); decompress [750, 950]. device 1: 100, 400,
    # 100. Means over the two chips, per call (2 calls), in ms.
    assert read("compress_ms.allreduce") == pytest.approx(150e-9 / 2 * 1e3)
    assert read("hop_ms.allreduce") == pytest.approx(400e-9 / 2 * 1e3)
    assert read("decompress_ms.allreduce") == pytest.approx(150e-9 / 2 * 1e3)


def test_a_while_and_its_nested_kernels_count_once(traces):
    events = {0: [("while.145", 0, 600), ("fusion.3", 100, 400),
                  ("while.148", 600, 1000), ("fusion.9", 700, 900),
                  ("fusion.11", 50, 150)]}
    run = traced_run(traces, events, {"calls": 1}, TRAIN_HLO)
    under = lambda **kw: scopes.seconds(run, scopes.under("model.blocks", **kw))
    assert under(backward=False) == pytest.approx(600e-9)  # not 900
    assert under(backward=True) == pytest.approx(400e-9)   # not 600
    assert under() == pytest.approx(1000e-9)


def test_transpose_splits_forward_from_backward(traces):
    events = {0: [("fusion.3", 0, 100), ("fusion.9", 100, 300),
                  ("fusion.11", 300, 340), ("fusion.12", 340, 400),
                  ("fusion.20", 400, 450), ("copy-start.1", 450, 470)]}
    counters = {"calls": 1, "tokens_per_step": 1, "flops_per_token": 1.0,
                "window_s": 1e-6}
    run = traced_run(traces, events, counters, TRAIN_HLO)
    read = lambda name: harness.metric_reader(name)(run)
    assert read("blocks_fwd_ms.train") == pytest.approx(100e-6)
    assert read("blocks_bwd_ms.train") == pytest.approx(200e-6)
    assert read("head_ms.train") == pytest.approx(100e-6)  # both passes
    assert read("optimizer_ms.train") == pytest.approx(50e-6)


def test_a_name_two_programs_share_keeps_each_events_op_name(traces):
    # the codec cell runs two programs; each has a copy.1 of its own
    events = {0: [("quantize_pack.1", 0, 100, "jit(<lambda>)/gz.compress/p"),
                  ("copy.1", 100, 300, "jit(<lambda>)/gz.compress/reshape"),
                  ("unpack_dequantize.1", 300, 350,
                   "jit(decompress)/gz.decompress/p"),
                  ("copy.1", 350, 400, "jit(decompress)/gz.decompress/reshape")]}
    d = write(traces, "codec", xspace(events))
    run = harness.TracedRun(
        trace=trace.reduce_dir(d, n_devices=1, op_categories={}),
        counters={"calls": 1}, peaks=PEAKS, chips=1)
    assert harness.metric_reader("compress_ms.codec")(run) == \
        pytest.approx(300e-6)
    assert harness.metric_reader("decompress_ms.codec")(run) == \
        pytest.approx(100e-6)


def test_the_runs_own_file_is_read(traces):
    # an older file of another cell, with another window, is not the run's
    big = 1_792_311_992_553_127_737  # ns, as a chip's clock gives them
    write(traces, "other", xspace({0: [("quantize_pack.2", 0, 900,
                                        "jit(f)/gz.compress/p")]}, t0=big - 10**9))
    run = traced_run(traces, ALLREDUCE_EVENTS, ALLREDUCE_COUNTERS,
                     ALLREDUCE_HLO, t0=big)
    assert harness.metric_reader("compress_ms.allreduce")(run) == \
        pytest.approx(150e-9 / 2 * 1e3)
    for p in traces.glob("**/*.xplane.pb"):
        p.unlink()
    scopes._scoped.cache_clear()
    assert harness.metric_reader("compress_ms.allreduce")(run) is None


def test_a_program_without_scopes_reports_nothing(traces):
    unscoped = ALLREDUCE_HLO.replace("gz.", "")
    for hlo in (unscoped, None):  # no codec scope; no op_name at all
        run = traced_run(traces, ALLREDUCE_EVENTS, ALLREDUCE_COUNTERS, hlo)
        for name in ("compress_ms.allreduce", "hop_ms.allreduce",
                     "decompress_ms.allreduce"):
            assert harness.metric_reader(name)(run) is None


@pytest.mark.parametrize("name", [
    "hop_roofline.allreduce", "permute_ms.allreduce", "wire_MB.allreduce",
    "idle_share.allreduce", "codec_roofline.codec", "idle_share.codec"])
def test_existing_readers_ignore_op_names(traces, name):
    read = harness.metric_reader(name)
    with_names = traced_run(traces, ALLREDUCE_EVENTS, ALLREDUCE_COUNTERS,
                            ALLREDUCE_HLO)
    got = read(with_names)
    assert got is not None
    # the same profile with no op_name on any event: the classes still
    # come from the HLO text, as the drivers give them
    without = traced_run(traces, ALLREDUCE_EVENTS, ALLREDUCE_COUNTERS,
                         ALLREDUCE_HLO.replace("op_name=", "op_nome="))
    assert got == read(without)


def test_union_length_matches_the_trace_reduction():
    import numpy as np

    rng = np.random.default_rng(7)
    starts = rng.integers(0, 10_000, 500)
    ends = starts + rng.integers(0, 300, 500)
    want = trace._length(trace._union(zip(starts.tolist(), ends.tolist())))
    assert scopes._union_ps(starts, ends) == want
    assert scopes._union_ps(starts[:0], ends[:0]) == 0
