"""The audited entry matrix: every ``VisualSystem`` entry point, traced
abstractly over entry × precision × masked × localize × fleet.

Each :class:`EntrySpec` names one program CI cares about, its launch
budget, and the ``launch_gate/*`` row names in ``BENCH_frontend.json``
whose runtime counts the static count must EQUAL (``restored_fleet``
reconciles against the plain fleet entry: a snapshot restore
repopulates state, never the launch graph — it dispatches the same
traced core).  ``trace_entry`` builds the session, makes the closed
jaxpr with ``jax.make_jaxpr`` over ``jax.ShapeDtypeStruct`` avals — no
data, no execution — and simultaneously runs the runtime
``ops.launch_audit`` counter so the report can prove the two agree.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_walk
from repro.core.pipeline import PipelineConfig, VisualSystem
from repro.core.rig import RigConfig
from repro.core.types import CameraIntrinsics, ORBConfig
from repro.kernels import ops

__all__ = ["EntrySpec", "TracedEntry", "MATRIX", "trace_entry",
           "trace_matrix"]


@dataclasses.dataclass(frozen=True)
class EntrySpec:
    """One audited program: which entry core, under which session
    configuration, with which launch budget, reconciled against which
    runtime gate rows."""

    name: str
    entry: str                   # VisualSystem.entry_core key
    precision: str = "f32"
    masked: bool = False
    localize: bool = False
    launch_budget: int = 3
    gates: tuple[str, ...] = ()
    note: str = ""


# Budgets: 3 per frame / fleet frame (1 dense FE + 1 sparse FE + 1
# fused FM), +1 with the localization backend, 1 for the FM stage
# alone, 2 for FE alone, 3 per scan step for sequences (seq_len=2
# below).  Gate names match benchmarks.run's launch_gate rows.
MATRIX: tuple[EntrySpec, ...] = (
    EntrySpec("frame_f32", "process_frame",
              gates=("quad_frame_launches",),
              note="one quad rig frame, f32 datapath"),
    EntrySpec("frame_f32_masked", "process_frame", masked=True,
              note="degraded rig frame: dead-camera mask is "
                   "elementwise jnp, same schedule"),
    EntrySpec("fleet_f32", "process_fleet",
              gates=("fleet_frame_launches",
                     "restored_fleet_frame_launches"),
              note="fleet frame; also reconciles the restored-service "
                   "gate — restore repopulates state, never the "
                   "launch graph"),
    EntrySpec("fleet_f32_masked", "process_fleet", masked=True,
              gates=("degraded_fleet_frame_launches",),
              note="fleet frame with dead cameras masked out"),
    EntrySpec("match_f32", "match", launch_budget=1,
              gates=("fm_frame_launches",),
              note="fused FM megakernel alone, both stereo pairs in "
                   "the grid"),
    EntrySpec("extract_f32", "extract", launch_budget=2,
              note="FE alone: 1 dense + 1 sparse launch"),
    EntrySpec("frame_u8", "process_frame", precision="uint8",
              gates=("u8_frame_launches",),
              note="uint8 integer datapath, same 3-launch schedule"),
    EntrySpec("fleet_u8", "process_fleet", precision="uint8",
              gates=("u8_fleet_frame_launches",),
              note="uint8 fleet frame"),
    EntrySpec("fleet_u8_masked", "process_fleet", precision="uint8",
              masked=True,
              note="uint8 degraded fleet frame"),
    EntrySpec("frame_loc", "process_frame", localize=True,
              launch_budget=4, gates=("loc_frame_launches",),
              note="localized frame: 3 frontend + 1 temporal-match "
                   "backend launch"),
    EntrySpec("fleet_loc", "process_fleet", localize=True,
              launch_budget=4, gates=("loc_fleet_frame_launches",),
              note="localized fleet frame: rigs fold into the one "
                   "temporal launch"),
    EntrySpec("run_f32", "run", launch_budget=6,
              note="T=2 sequence, sequential schedule: the scan body "
                   "multiplies the 3-launch frame"),
    EntrySpec("run_fleet_f32", "run_fleet", launch_budget=6,
              note="T=2 fleet sequence"),
)


@dataclasses.dataclass
class TracedEntry:
    """One matrix entry's abstract trace plus both launch counts: the
    static jaxpr-walk count and the runtime ``launch_audit`` counter
    observed during the same trace (internal cross-check — they must
    agree before either is compared to the benchmark artifact)."""

    spec: EntrySpec
    closed: jax.extend.core.ClosedJaxpr
    sites: list[jaxpr_walk.PallasSite]
    count: jaxpr_walk.LaunchCount
    audit_count: int


def _session(spec: EntrySpec, height: int, width: int,
             max_features: int) -> VisualSystem:
    cfg = ORBConfig(height=height, width=width,
                    max_features=max_features)
    intr = CameraIntrinsics(cx=width / 2.0, cy=height / 2.0)
    return VisualSystem(
        RigConfig.quad(intr),
        PipelineConfig(orb=cfg, precision=spec.precision,
                       localize=spec.localize))


def _entry_avals(vs: VisualSystem, spec: EntrySpec, n_rigs: int,
                 seq_len: int) -> tuple:
    h, w = vs.pipe.orb.height, vs.pipe.orb.width
    c = vs.rig.n_cameras
    dt = jnp.uint8 if spec.precision == "uint8" else jnp.float32
    sds = jax.ShapeDtypeStruct
    if spec.entry == "process_frame":
        avals = (sds((c, h, w), dt),)
        if spec.masked:
            avals += (sds((c,), jnp.bool_),)
        return avals
    if spec.entry == "process_fleet":
        avals = (sds((n_rigs, c, h, w), dt),)
        if spec.masked:
            avals += (sds((n_rigs, c), jnp.bool_),)
        return avals
    if spec.entry == "extract":
        return (sds((c, h, w), dt),)
    if spec.entry == "match":
        # Feature avals come from the FE core's own abstract output —
        # the matrix never hand-writes FeatureSet shapes.
        feats = jax.eval_shape(vs.entry_core("extract"), sds((c, h, w), dt))
        p = vs.rig.n_pairs
        pair = jax.tree.map(
            lambda s: sds((p,) + s.shape[1:], s.dtype), feats)
        img = sds((p, h, w), dt)
        return (img, img, pair, pair)
    if spec.entry == "run":
        return (sds((seq_len, c, h, w), dt),)
    if spec.entry == "run_fleet":
        return (sds((seq_len, n_rigs, c, h, w), dt),)
    raise ValueError(f"unknown entry {spec.entry!r}")


def trace_entry(spec: EntrySpec, height: int = 720, width: int = 1280,
                max_features: int = 1000, n_rigs: int = 2,
                seq_len: int = 2) -> TracedEntry:
    """Abstractly trace one matrix entry under impl='pallas'."""
    vs = _session(spec, height, width, max_features)
    core = vs.entry_core(spec.entry, impl="pallas")
    avals = _entry_avals(vs, spec, n_rigs, seq_len)
    with ops.launch_audit() as audit:
        closed = jax.make_jaxpr(core)(*avals)
    return TracedEntry(
        spec=spec,
        closed=closed,
        sites=jaxpr_walk.pallas_sites(closed),
        count=jaxpr_walk.count_launches(closed),
        audit_count=audit.count)


def trace_matrix(specs: tuple[EntrySpec, ...] = MATRIX,
                 **kwargs) -> list[TracedEntry]:
    return [trace_entry(spec, **kwargs) for spec in specs]
