"""Disparity -> depth -> rig-frame 3-D points.

The frontend's ``DepthSet`` is per stereo pair in the pair's LEFT
camera frame; the pose solve wants ONE point cloud per rig.  This
module lifts every pair's matched features through the pair's
intrinsics and folds them into the shared rig frame via
``RigConfig.pair_rotations`` (the quad rig's back pair looks along -z,
so its points rotate 180 degrees about y before fusing with the front
pair's).  Everything is elementwise / small-matmul jnp — the stage adds
ZERO kernel launches and batches over arbitrary leading axes
(fleet rigs, time, both).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rig import RigConfig


def backproject(xy: jnp.ndarray, depth: jnp.ndarray,
                fx, fy, cx, cy) -> jnp.ndarray:
    """Pinhole back-projection: (..., K, 2) pixel coords + (..., K)
    depth -> (..., K, 3) camera-frame points.  An invalid lane's depth
    is exactly 0 (``matching._depth_set``), so its point is exactly the
    origin — never a division, never NaN."""
    x = (xy[..., 0] - cx) / fx * depth
    y = (xy[..., 1] - cy) / fy * depth
    return jnp.stack([x, y, depth], axis=-1)


def rig_points(xy: jnp.ndarray, depth: jnp.ndarray,
               rig: RigConfig) -> jnp.ndarray:
    """Per-pair left-feature coords + depths -> rig-frame points.

    ``xy``: (..., n_pairs, K, 2) level-0 pixel coords of the left
    features; ``depth``: (..., n_pairs, K) from the pair's ``DepthSet``.
    Returns (..., n_pairs, K, 3) points in the RIG frame: back-projected
    through each pair's left-camera intrinsics, then rotated by the
    pair's camera->rig rotation.  (The scene rig's left cameras sit at
    the rig origin, so rotation alone closes the frame change.)"""
    if xy.shape[-3] != rig.n_pairs:
        raise ValueError(
            f"rig_points: xy pair axis is {xy.shape[-3]} but the rig "
            f"has {rig.n_pairs} pairs")
    intr = rig.pair_intrinsics
    fx = jnp.asarray([ic.fx for ic in intr], jnp.float32)[:, None]
    fy = jnp.asarray([ic.fy for ic in intr], jnp.float32)[:, None]
    cx = jnp.asarray([ic.cx for ic in intr], jnp.float32)[:, None]
    cy = jnp.asarray([ic.cy for ic in intr], jnp.float32)[:, None]
    cam = backproject(xy, depth, fx, fy, cx, cy)
    rot = jnp.asarray(rig.pair_rotation_array())
    # HIGHEST: a default f32 einsum on the TPU runs in bf16 passes,
    # which would round the points (and so the pose) to ~3 digits.
    return jnp.einsum("pji,...pki->...pkj", rot, cam,
                      precision=jax.lax.Precision.HIGHEST)


def gt_relative_pose(r_prev: np.ndarray, t_prev: np.ndarray,
                     r_curr: np.ndarray, t_curr: np.ndarray):
    """Ground-truth relative pose between two rig poses (R: rig->world,
    t: world position), in the convention the solver estimates:
    ``p_curr = R_rel @ p_prev + t_rel`` over rig-frame points."""
    r_prev = np.asarray(r_prev, np.float64)
    r_curr = np.asarray(r_curr, np.float64)
    r_rel = r_curr.T @ np.asarray(r_prev, np.float64)
    t_rel = r_curr.T @ (np.asarray(t_prev, np.float64)
                        - np.asarray(t_curr, np.float64))
    return r_rel, t_rel
