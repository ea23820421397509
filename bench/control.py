#!/usr/bin/env python3
"""Read a cell's control on the chip: the plain reference, computed in the
precision below the one the configuration states, put in the program's
place and compared exactly as the program's answers are.

    python3 bench/control.py --workload <name> --seeds 11,12,13

One line per seed gives each number compared beside its limit; the
control has to fail at least one of them. Where the cell's driver defines
``faults``, the readings of those faults, planted in the reference put in
the program's place, follow on lines of their own. The benchmark's own
runs never run this; it is how the limits' upper readings were taken.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.resolve_cell(harness.load_spec(ROOT), args.workload,
                                    ROOT)
        harness.import_program(ROOT)
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            raise harness.BenchError("the control runs on the cell's chips")
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    driver = harness.driver_module(cell, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(cell=cell, seed=seed,
                              devices=devices[:cell.chips], root=ROOT)
        readings = {"control": driver.control(ctx)}
        if hasattr(driver, "faults"):
            readings.update(driver.faults(ctx))
        for what, checks in readings.items():
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "reading": what,
                              "fails": any(not c.ok for c in checks),
                              "checks": {c.name: {"value": c.value,
                                                  "limit": c.limit}
                                         for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
