"""Localization backend: depth + ego-motion on top of the frontend.

The paper's frontend exists to feed a localization backend; this
package closes that loop on the session API:

  ``geometry``  disparity -> depth -> RIG-FRAME 3-D points (all stereo
                pairs fused through ``RigConfig.pair_rotations``; pure
                jnp, zero extra launches);
  ``pose``      temporal-match ego-motion — ONE fused match-only
                launch for every pair of every rig, then a batched
                robust (masked top-K reweighted) Procrustes solve,
                vmapped over rigs; degenerate inputs yield identity +
                ``valid=False``, never NaN;
  ``metrics``   ATE / RPE trajectory error vs ``data.scenes`` ground
                truth, host float64 — the accuracy gates CI enforces
                for both f32 and uint8 precision.

``VisualSystem`` (with ``PipelineConfig(localize=True)``) wires these
into ``process_frame`` / ``process_fleet`` / ``run`` so a localized
frame costs at most 3 frontend + 1 backend launches; the helpers below
convert between outputs and the cross-frame ``LocalizationState``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import matching
from repro.core.types import (LocalizationOutput, LocalizationState,
                              PoseSet)
from repro.localization import geometry, metrics, pose
from repro.localization.geometry import rig_points
from repro.localization.metrics import ACCURACY_LIMITS, trajectory_metrics
from repro.localization.pose import (MIN_CORRESPONDENCES, solve_pose,
                                     solve_pose_batched)

__all__ = [
    "geometry", "metrics", "pose",
    "rig_points", "trajectory_metrics", "ACCURACY_LIMITS",
    "MIN_CORRESPONDENCES", "solve_pose", "solve_pose_batched",
    "PoseSet", "LocalizationOutput", "LocalizationState",
    "state_from", "zero_state",
]


def state_from(out: LocalizationOutput) -> LocalizationState:
    """The cross-frame memory a ``LocalizationOutput`` leaves behind:
    its left descriptors + matcher meta, rig-frame points, and the
    combined feature-and-depth usability mask.  Works on any slice
    (a fleet output, or one rig's ``jax.tree.map(lambda x: x[b], ...)``
    row) — this is how ``serving.FleetService`` carries per-rig state
    across re-bucketed batches."""
    feat_l = out.stereo.features_l
    return LocalizationState(
        desc=feat_l.desc, meta=matching._meta(feat_l),
        points=out.points,
        valid=feat_l.valid & out.stereo.depth.valid)


def zero_state(n_pairs: int, k: int, n_rigs: int | None = None
               ) -> LocalizationState:
    """An all-invalid previous-frame state (session start, or a rig the
    service has never served): zero arrays with ``valid=False``
    everywhere, so the first temporal solve degenerates to identity +
    ``valid=False`` through the SAME jitted graph as a normal frame."""
    lead = (n_pairs,) if n_rigs is None else (n_rigs, n_pairs)
    return LocalizationState(
        desc=jnp.zeros(lead + (k, 8), jnp.uint32),
        meta=jnp.zeros(lead + (k, 4), jnp.float32),
        points=jnp.zeros(lead + (k, 3), jnp.float32),
        valid=jnp.zeros(lead + (k,), bool))
