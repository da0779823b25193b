"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program (``src/repro``).
"""

from __future__ import annotations

import sys

from benchmarks.chip.harness import ROOT, SpecError


def require() -> None:
    """Put the program on the path; a checkout without it is an error."""
    if not (ROOT / "src" / "repro" / "core" / "pipeline.py").is_file():
        raise SpecError(f"no program: {ROOT / 'src' / 'repro'} is missing")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def session(config: dict):
    """A ``VisualSystem`` for the configuration's rig and frontend."""
    require()
    from repro.core import (CameraIntrinsics, ORBConfig, PipelineConfig,
                            RigConfig, VisualSystem)
    rig = config["rig"]
    cam = CameraIntrinsics(**config["camera"])
    pairs = tuple(tuple(p) for p in rig["pairs"])
    rot = tuple(tuple(tuple(row) for row in r) for r in rig["pair_rotations"])
    return VisualSystem(
        RigConfig(n_cameras=rig["n_cameras"], pairs=pairs, intrinsics=cam,
                  pair_rotations=rot),
        PipelineConfig(orb=ORBConfig(**config["orb"]), impl=config["impl"],
                       precision=config["precision"],
                       localize=config["localize"],
                       temporal_radius=config["temporal_radius"]))

