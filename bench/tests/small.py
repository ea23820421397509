"""Small sizes at which the tests run each cell on the CPU."""
import time

from bench import harness

SMOKE_MODEL = {
    "d_model": 128, "n_layer": 2, "vocab_size": 512, "d_state": 16,
    "headdim": 32,
    "program": {"arch": "mamba2-780m", "smoke": True, "fsdp": False,
                "remat": "full", "grad_policy": "auto"},
}
TRAFFIC = {
    "codec": {"elements": 16384},
    "allreduce": {"elements_per_rank": 32768, "baseline_seconds": 0.05},
    "train": {"seq": 256},
}


def spec() -> dict:
    s = harness.load_spec()
    setup = next(m for m in s["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup  # setup_s is every cell's
    return s


def cell(name: str):
    s = spec()
    w = next(w for w in s["workloads"] if w["name"] == name)
    config = SMOKE_MODEL if w["config"].startswith("mamba2") else {}
    probe = harness.resolve_cell(s, name)
    return harness.resolve_cell(s, name,
                                traffic_overrides=TRAFFIC[probe.traffic["driver"]],
                                config_overrides=config)


def run(name: str, *, seed: int = 2**33 + 7, trace: bool = False,
        driver_cls=None, seconds: float = 0.5):
    import jax

    c = cell(name)
    lines = []
    result = harness.run_cell(
        c, seed=seed, seconds=seconds, trace=trace,
        devices=jax.devices()[:c.chips], t_start=time.perf_counter(),
        log=lines.append, driver_cls=driver_cls,
        peaks=harness.load_peaks("TPU v5 lite"))
    return result, lines
