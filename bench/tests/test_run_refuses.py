"""run.py prints no result without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(root, workload="codec.lorenzo.16MiB.smooth"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=root)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj


def test_no_tpu_no_result():
    _no_result(_run(harness.ROOT))


def test_bench_alone_no_result(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    _no_result(p)
    assert "no program sources" in p.stderr
