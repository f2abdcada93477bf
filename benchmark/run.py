"""Run one benchmark cell and print its result as the last line.

    python3 benchmark/run.py --workload crush10k.remap_rep3 \
        --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a machine with the chips the cell
asks for.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the
window.  Exits non-zero, with no result line, when JAX finds no TPU or
fewer chips than the cell needs.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmark.lib import harness  # noqa: E402
from benchmark.lib.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    harness.emit_and_exit(line)


if __name__ == "__main__":
    sys.exit(main())
