"""Optimization backend — pose estimation from frontend output.

The paper offloads the frontend and leaves the backend (SLAM / VIO /
Registration) on CPU; to make the localization system end-to-end (and to
reproduce the Tab. I frontend/backend latency split) we implement a
compact stereo visual-odometry backend in JAX:

  stereo depth -> 3-D landmarks -> temporal descriptor matching ->
  weighted Kabsch (closed-form SE(3)) -> optional Gauss-Newton
  reprojection refinement -> trajectory integration.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import CameraIntrinsics, DepthSet, FeatureSet


def triangulate(feat_l: FeatureSet, depth: DepthSet,
                intr: CameraIntrinsics) -> jnp.ndarray:
    """Back-project left features with stereo depth -> (K, 3) points."""
    z = depth.depth
    x = (feat_l.xy[:, 0] - intr.cx) / intr.fx * z
    y = (feat_l.xy[:, 1] - intr.cy) / intr.fy * z
    return jnp.stack([x, y, z], axis=-1)


def kabsch(pts_a: jnp.ndarray, pts_b: jnp.ndarray,
           weights: jnp.ndarray):
    """Weighted closed-form rigid alignment: find (R, t) minimizing
    sum_i w_i || R a_i + t - b_i ||^2.  pts: (K, 3); weights: (K,)."""
    w = weights / jnp.maximum(jnp.sum(weights), 1e-6)
    ca = jnp.sum(w[:, None] * pts_a, axis=0)
    cb = jnp.sum(w[:, None] * pts_b, axis=0)
    a0 = pts_a - ca
    b0 = pts_b - cb
    # f32 products throughout: on the TPU a default-precision f32
    # matmul multiplies in bf16 passes, which would round the pose.
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    h = mm((w[:, None] * a0).T, b0)                   # (3, 3)
    u, _, vt = jnp.linalg.svd(h)
    d = jnp.sign(jnp.linalg.det(mm(vt.T, u.T)))
    s = jnp.diag(jnp.asarray([1.0, 1.0, 1.0])).at[2, 2].set(d)
    r = mm(mm(vt.T, s), u.T)
    t = cb - mm(r, ca)
    return r, t


def reprojection_residuals(r, t, pts_a, xy_b, intr: CameraIntrinsics):
    p = pts_a @ r.T + t
    z = jnp.maximum(p[:, 2], 1e-3)
    u = intr.fx * p[:, 0] / z + intr.cx
    v = intr.fy * p[:, 1] / z + intr.cy
    return jnp.stack([u - xy_b[:, 0], v - xy_b[:, 1]], axis=-1)


def _so3_exp(w: jnp.ndarray) -> jnp.ndarray:
    # sinc-form exponential map: differentiable at w = 0 (GN linearizes
    # around zero delta, so the naive norm form would emit NaN grads).
    theta2 = jnp.dot(w, w)
    theta = jnp.sqrt(theta2 + 1e-16)
    k = jnp.asarray([[0.0, -w[2], w[1]],
                     [w[2], 0.0, -w[0]],
                     [-w[1], w[0], 0.0]])
    a = jnp.sin(theta) / theta
    b = (1.0 - jnp.cos(theta)) / (theta2 + 1e-16)
    return jnp.eye(3) + a * k + b * (k @ k)


def gauss_newton_refine(r, t, pts_a, xy_b, weights,
                        intr: CameraIntrinsics, iters: int = 8,
                        huber_px: float = 5.0, damping: float = 1e-2):
    """Damped (Levenberg) GN on reprojection error over se(3), with a
    Huber robust loss: per-point weight is scaled by min(1, c/|res|), so
    gross mismatches cannot explode the normal equations."""

    def step(carry, _):
        r_c, t_c = carry
        res_c = reprojection_residuals(r_c, t_c, pts_a, xy_b, intr)
        norm = jnp.linalg.norm(res_c, axis=-1)
        w_rob = weights * jnp.minimum(1.0, huber_px
                                      / jnp.maximum(norm, 1e-6))

        def flat_res(delta):
            r_d = _so3_exp(delta[:3]) @ r_c
            t_d = t_c + delta[3:]
            res = reprojection_residuals(r_d, t_d, pts_a, xy_b, intr)
            return (res * w_rob[:, None]).reshape(-1)

        zero = jnp.zeros((6,))
        res0 = flat_res(zero)
        jac = jax.jacfwd(flat_res)(zero)              # (2K, 6)
        jtj = jac.T @ jac
        lm = jtj + damping * jnp.diag(jnp.diag(jtj)) + 1e-6 * jnp.eye(6)
        delta = -jnp.linalg.solve(lm, jac.T @ res0)
        return (_so3_exp(delta[:3]) @ r_c, t_c + delta[3:]), None

    (r_f, t_f), _ = jax.lax.scan(step, (r, t), None, length=iters)
    return r_f, t_f


class PoseEstimate(NamedTuple):
    rotation: jnp.ndarray       # (3, 3)
    translation: jnp.ndarray    # (3,)
    inliers: jnp.ndarray        # scalar int32


def _masked_median(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Median of x over mask (static shape: sort with +inf fill)."""
    n = jnp.sum(mask.astype(jnp.int32))
    filled = jnp.where(mask, x, jnp.inf)
    s = jnp.sort(filled)
    mid = jnp.maximum(n - 1, 0) // 2
    return s[mid]


def estimate_relative_pose(pts_prev: jnp.ndarray, pts_curr: jnp.ndarray,
                           weights: jnp.ndarray, xy_curr: jnp.ndarray,
                           intr: CameraIntrinsics,
                           refine: bool = True,
                           robust_iters: int = 3,
                           gate_scale: float = 4.0) -> PoseEstimate:
    """(R, t) mapping previous-frame points into the current frame.

    Robust cascade (descriptor mismatches and stereo depth quantization
    produce metre-scale 3-D outliers, so plain least squares would be
    poisoned):
      1. translation-first init — at VO frame rates R ~ I, so the
         per-axis masked median of the displacement field is a robust t;
      2. gate 3-D residuals at ``gate_scale`` x median, iterate Kabsch;
      3. gate reprojection residuals, damped Huber Gauss-Newton refine.
    """
    mask0 = weights > 0

    # 1. robust translation-only init (R = I)
    disp = pts_curr - pts_prev                        # (K, 3)
    t0 = jnp.stack([_masked_median(disp[:, i], mask0) for i in range(3)])
    res0 = jnp.linalg.norm(disp - t0, axis=-1)
    med0 = _masked_median(res0, mask0)
    w = jnp.where(res0 <= gate_scale * jnp.maximum(med0, 1e-2),
                  weights, 0.0)

    # 2. gated Kabsch rounds
    def round_(w_c, _):
        r_n, t_n = kabsch(pts_prev, pts_curr, w_c)
        res = jnp.linalg.norm(pts_prev @ r_n.T + t_n - pts_curr, axis=-1)
        med = _masked_median(res, w_c > 0)
        gate = res <= gate_scale * jnp.maximum(med, 1e-3)
        return jnp.where(gate, weights, 0.0), None

    w, _ = jax.lax.scan(round_, w, None, length=robust_iters)
    r, t = kabsch(pts_prev, pts_curr, w)
    if refine:
        # 3. gate reprojection residuals, then damped-Huber Gauss-Newton
        res = jnp.linalg.norm(
            reprojection_residuals(r, t, pts_prev, xy_curr, intr), axis=-1)
        med = _masked_median(res, w > 0)
        w = jnp.where(res <= gate_scale * jnp.maximum(med, 1.0), w, 0.0)
        r, t = gauss_newton_refine(r, t, pts_prev, xy_curr, w, intr)
    return PoseEstimate(r, t, jnp.sum((w > 0).astype(jnp.int32)))


def integrate_trajectory(poses: list[PoseEstimate]) -> jnp.ndarray:
    """Chain relative poses into world positions (T+1, 3), origin start.

    Relative pose maps prev-frame coords to curr-frame coords; the camera
    position therefore updates as p_w <- p_w - R_w t_rel with
    R_w <- R_w R_rel^-1 (standard VO composition).
    """
    pos = [jnp.zeros((3,))]
    r_w = jnp.eye(3)
    for p in poses:
        r_w = r_w @ p.rotation.T
        pos.append(pos[-1] - r_w @ p.translation)
    return jnp.stack(pos)
