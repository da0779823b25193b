"""Shared pieces of the CPU tests that drive whole benchmark runs: the
cells cut to a tiny frame on the jnp path, and the planted faults."""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import control, harness, program  # noqa: E402


def tiny(name: str):
    """The cell at 96x160 with 64 features, on the jnp path (``ref``)."""
    cell = harness.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg["orb"].update(height=96, width=160, max_features=64)
    cfg["camera"].update(fx=115.2, fy=115.2, cx=80.0, cy=48.0)
    cfg["impl"] = "ref"
    return cell._replace(config=cfg)


def run(name: str, factory=None, seed: int = 3_000_000_123):
    return harness.run_cell(tiny(name), seed, 0.5, False,
                            time.perf_counter(), require_tpu=False,
                            session_factory=factory, log=lambda m: None)


def _flip_first_descriptor(out):
    """The first keypoint's descriptor words with bit 0 flipped."""
    st = getattr(out, "stereo", out)
    fl = st.features_l
    idx = (0,) * (fl.desc.ndim - 1)
    st = st._replace(features_l=fl._replace(
        desc=fl.desc.at[idx].set(fl.desc[idx] ^ 1)))
    return out._replace(stereo=st) if hasattr(out, "stereo") else st


def faulty(kind: str):
    """A session factory planting one fault in the timed path:
    ``answer`` alters an answer where it is produced, ``state`` keeps the
    localization state from advancing."""

    def factory(config):
        base = program.session(config)

        class Faulty(type(base)):
            def process_frame(self, images, *a, **k):
                if kind == "state":
                    self.reset_localization()
                out = super().process_frame(images, *a, **k)
                return _flip_first_descriptor(out) if kind == "answer" else out

        return Faulty(base.rig, base.pipe)

    return factory


CONTROL = control.control_session
