"""The bench's own tests run on the CPU, with four virtual devices for the
cells that span chips:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from bench import harness  # noqa: E402

harness.import_program(ROOT)
