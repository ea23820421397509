"""BENCHMARK.json against the files the harness finds by name."""
import json
import re
import shutil

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = harness.load_spec()


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_files_and_reports_enough(w):
    cell = harness.resolve_cell(SPEC, w["name"])
    assert (harness.BENCH / "drivers" / f"{cell.traffic['driver']}.py").is_file()
    assert (harness.BENCH / "configs" / f"{cell.config_name}.ref.py").is_file()
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_has_a_reader_and_its_cells_report_what_it_moves(m):
    assert callable(harness.metric_reader(m["name"]))
    moves = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
    cells = m.get("workloads", [w["name"] for w in SPEC["workloads"]])
    for name in cells:
        assert name in moves.get("workloads", [name])
        cell = harness.resolve_cell(SPEC, name)
        assert m["name"] in {x["name"] for x in cell.per_layer}


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    return root, json.loads(json.dumps(SPEC))


def test_a_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later change adds files and entries only; the harness finds them."""
    root, spec = _checkout(tmp_path)
    spec["configs"].append({"name": "gz-new", "source": "x",
                            "file": "bench/configs/gz-new.json",
                            "reduced": [], "why": "x"})
    (root / "bench" / "configs" / "gz-new.json").write_text(
        json.dumps({"gz": {"eb": 1e-3}, "limits": {}}))
    (root / "bench" / "configs" / "gz-new.ref.py").write_text("X = 1\n")
    (root / "bench" / "traffic" / "codec.1MiB.rough.json").write_text(
        json.dumps({"driver": "codec", "elements": 262144,
                    "field": "gaussian", "pool": 2, "sample": 2}))
    (root / "bench" / "metrics" / "new_metric.codec.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec["workloads"].append({"name": "codec.new", "config": "gz-new",
                              "traffic": "codec.1MiB.rough", "chips": 1,
                              "why": "x"})
    spec["end_to_end"][[m["name"] for m in spec["end_to_end"]].index(
        "codec_GBps")]["workloads"].append("codec.new")
    spec["per_layer"].append({"name": "new_metric.codec", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "codec_GBps",
                              "workloads": ["codec.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve_cell(harness.load_spec(root), "codec.new", root)
    assert cell.config["gz"]["eb"] == 1e-3
    assert cell.traffic["field"] == "gaussian"
    assert [m["name"] for m in cell.per_layer] == ["new_metric.codec"]
    assert harness.metric_reader("new_metric.codec", root)(None) == 42.0
    assert harness.driver_module(cell, root).Driver is not None
    assert harness.reference_module(cell, root).X == 1


def test_a_new_model_config_is_found_by_name(tmp_path):
    """A training configuration of another architecture brings its shape
    check and model FLOPs in its own reference: the train driver and the
    mfu reader name no architecture's keys."""
    root, spec = _checkout(tmp_path)
    spec["configs"].append({"name": "toy-lm", "source": "x",
                            "file": "bench/configs/toy-lm.json",
                            "reduced": [], "why": "x"})
    (root / "bench" / "configs" / "toy-lm.json").write_text(json.dumps(
        {"vocab_size": 64, "width": 8, "limits": {}}))
    (root / "bench" / "configs" / "toy-lm.ref.py").write_text(
        "def check_model(cfg, mcfg):\n    return []\n\n\n"
        "def flops_per_token(cfg):\n    return 6.0 * cfg['width']\n")
    spec["workloads"].append({"name": "train.toy", "config": "toy-lm",
                              "traffic": "train.seq2048.batch2", "chips": 1,
                              "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve_cell(harness.load_spec(root), "train.toy", root)
    ref = harness.reference_module(cell, root)
    assert ref.check_model(cell.config, None) == []
    assert ref.flops_per_token(cell.config) == 48.0
    assert harness.driver_module(cell, root).Driver is not None
    arch_keys = ("d_model", "n_layer", "d_state", "headdim", "expand",
                 "d_conv", "chunk_size", "ssm")
    for f in ("drivers/train.py", "metrics/mfu.train.py"):
        src = (harness.BENCH / f).read_text()
        assert not [k for k in arch_keys if k in src], f


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.load_peaks("cpu")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
