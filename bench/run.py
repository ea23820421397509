#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics come from
``BENCHMARK.json`` at the root of the checkout. Set-up (inputs and weights
made on the device from ``--seed``, compilation or the persistent compile
cache, warm-up) is timed as ``setup_s``; then the cell's driver runs a
closed loop for ``--seconds``. ``--trace 0`` reports the end-to-end
metrics, ``--trace 1`` profiles the window and reports the per-layer
metrics and a breakdown. The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and ``checks`` (each number compared
with the plain reference, beside its limit), which also end stderr.

Without a TPU, with fewer chips than the cell asks for, on a device kind
missing from ``bench/peaks.json``, or outside a checkout that holds the
program's sources, the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = harness.load_spec(ROOT)
        cell = harness.resolve_cell(spec, args.workload, ROOT)
        harness.import_program(ROOT)
        import jax

        from repro.launch.compile_cache import enable_compile_cache

        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise harness.BenchError(
                f"no TPU found (JAX platform is {devices[0].platform!r}); "
                "the benchmark runs only on the chip")
        if len(devices) < cell.chips:
            raise harness.BenchError(
                f"cell {cell.name} needs {cell.chips} chips, found "
                f"{len(devices)}")
        harness.load_peaks(devices[0].device_kind, ROOT)
        print(f"cell {cell.name}: {cell.chips} x {devices[0].device_kind}; "
              f"jax {jax.__version__}; compile cache {cache}; "
              f"seed {args.seed}", flush=True)
        result = harness.run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), devices=devices[:cell.chips],
            t_start=T_START, root=ROOT)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
