"""Chip benchmark of the quad-camera visual system.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip this process finds and
prints the result as the last line of standard output: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  Exits
non-zero, with no result, when JAX finds no TPU or fewer chips than the
cell asks for, or when a part of the cell has no file.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        cell = harness.load_cell(args.workload)
        from benchmarks.chip import program
        program.require()
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START, log=log)
    except (harness.SpecError, harness.NoChip) as e:
        log(f"chipbench: {e}")
        return 2
    harness.write_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
