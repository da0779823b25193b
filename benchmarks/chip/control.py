"""Readings that the limits of ``correct`` are set from.

    python3 -m benchmarks.chip.control --workload <cell> \\
        --seeds 11 12 ... --control-seeds 21 22 23 --seconds 3

For each ``--seeds`` seed, one run of the cell as the benchmark makes
it (a short window): the compared numbers of the program, whose largest
is the lower reading.  For each ``--control-seeds`` seed, the same run
with the control in the program's place: the plain reference computed
one precision below the configuration's, on frames cut from 8 to 4 bits
a pixel (the uint8 datapath's next step down), whose smallest reading
is the upper one.  One JSON line per run on standard output; every run
shares one process, so set-up is paid once.  Not part of the
benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, program, reference  # noqa: E402

LOW_BITS = 0xF0          # keep 4 of 8 bits per pixel


def control_session(config: dict):
    """A session whose answers come from the reference on 4-bit frames."""
    program.require()
    import jax
    import jax.numpy as jnp
    from repro.core import VisualSystem

    cfg = dict(config["orb"], temporal_radius=config["temporal_radius"])
    rig = dict(config["camera"], **config["rig"])
    frame = jax.jit(lambda im: reference.rig_frame(
        jnp.asarray(im) & LOW_BITS, cfg, rig))
    loc = jax.jit(lambda st, pv: reference.localize(st, pv, cfg, rig))

    class Control(VisualSystem):
        def process_frame(self, images, *a, **k):
            ans = frame(images)
            if not self.pipe.localize:
                return ans
            if getattr(self, "_prev", None) is None:
                self._prev = reference.zero_state(len(rig["pairs"]),
                                                  cfg["max_features"])
            ans, self._prev = loc(ans, self._prev)
            return ans

        def reset_localization(self):
            self._prev = None

    base = program.session(config)
    return Control(base.rig, base.pipe)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    cell = harness.load_cell(args.workload)
    runs = ([(s, "program", None) for s in args.seeds]
            + [(s, "control", control_session) for s in args.control_seeds])
    for seed, side, factory in runs:
        t = time.perf_counter()
        res = harness.run_cell(cell, seed, args.seconds, False, t,
                               session_factory=factory, log=log)
        print(json.dumps({"workload": cell.name, "seed": seed, "side": side,
                          "correct": res["correct"],
                          "answers": res["attempted"],
                          "checks": res["checks"],
                          "metrics": res["metrics"],
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
