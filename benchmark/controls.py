"""Readings of a cell's check for sound runs, for its control and for
planted faults, many seeds in one process.

    python3 benchmark/controls.py --workload rados12_ec83.degraded_read_4m \
        --seconds 10 --seeds 11,12,13 --modes program control

Each mode runs every seed once through the same harness as
``benchmark/run.py`` (set-up, window, check), with the mode's patch
held open around the window only: ``program`` patches nothing,
``control`` plants the generator's control and the other names plant the
faults of ``benchmark/lib/faults.py``.  Prints one JSON line per run
with the numbers the check compared.  Needs a TPU, like the benchmark;
the benchmark's own runs never run this.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.lib import faults, harness  # noqa: E402
from benchmark.lib.spec import load_cell  # noqa: E402


def patch_for(generator: str, mode: str):
    if mode == "program":
        return None
    if mode == "control":
        return faults.CONTROLS[generator]
    return faults.FAULTS[generator][mode]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmark/controls.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated whole numbers")
    p.add_argument("--modes", nargs="+", default=["program"])
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    generator = cell.traffic["generator"]
    for mode in args.modes:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.monotonic()
            try:
                line = harness.run_cell(
                    cell, seed, args.seconds, False, t0,
                    window_patch=patch_for(generator, mode))
            except harness.NoChip as e:
                print(f"controls: {e}", file=sys.stderr)
                return 2
            except Exception as e:  # a crashed control has failed
                print(json.dumps({"workload": args.workload,
                                  "mode": mode, "seed": seed,
                                  "error": f"{type(e).__name__}: {e}"}),
                      flush=True)
                continue
            print(json.dumps({
                "workload": args.workload, "mode": mode, "seed": seed,
                "correct": line["correct"], "check": line["check"],
                "attempted": line["attempted"],
                "failed": line["failed"],
                "metrics": {k: v["value"]
                            for k, v in line["metrics"].items()},
                "wall_s": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
