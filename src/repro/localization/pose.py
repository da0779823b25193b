"""Temporal-match ego-motion: batched robust Procrustes pose solve.

One rig's solve consumes the temporal correspondences between the
previous frame's rig-frame points and the current frame's (both from
``geometry.rig_points``) and returns the relative SE(3) motion as a
``PoseSet``.  The solver is a masked top-K reweighting loop around the
weighted Kabsch alignment (``core.backend.kabsch``): each round keeps
the ``keep_frac`` fraction of correspondences with the smallest 3-D
residual (static-shape sort with +inf fill, the ``_masked_median``
idiom) and re-solves, so metre-scale outliers from descriptor aliasing
or stereo quantization cannot poison the least squares.

Degeneracy is data, not control flow: fewer than
``MIN_CORRESPONDENCES`` usable matches, a collapsed point cloud (e.g. a
zero-baseline rig whose depths are all 0), or any non-finite input
yields EXACTLY identity + ``valid=False`` — never NaN — so the first
frame of a session, an all-dead rig, and a textureless scene all flow
through the same jitted graph.  ``solve_pose_batched`` vmaps the solve
over a leading rig axis; the temporal matching itself
(``temporal_correspondences``) is ONE fused match-only kernel launch
for every pair of every rig.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import backend
from repro.core.types import LocalizationState, ORBConfig, PoseSet
from repro.kernels import ops

#: A rigid transform has 6 DoF; 3 point correspondences are the minimum
#: that determines it.  Below this the solve is identity + invalid.
MIN_CORRESPONDENCES = 3


def temporal_correspondences(prev: LocalizationState,
                             curr: LocalizationState, cfg: ORBConfig,
                             search_radius: float,
                             search_radius_y: float,
                             impl: str | None = None):
    """Match prev -> curr left features and gather paired 3-D points.

    ``prev``/``curr`` carry FLAT (B, K, ...) axes where B folds every
    pair of every rig — the whole fleet's temporal matching is ONE
    fused match-only launch (the [0, max_disparity] window is reused as
    [-r, +r] by shifting the previous x coords, exactly like
    ``VisualSystem.temporal_match``).  Returns ``(pts_prev, pts_curr,
    weights)``, each (B, K, ...): weights are 1.0 where the match
    passed the Hamming gate AND both endpoints carry valid
    feature+depth, else 0.0."""
    meta_a = prev.meta.at[..., 0].add(search_radius)
    dist, idx = ops.match_rectify_fused(
        prev.desc, meta_a, curr.desc, curr.meta,
        row_band=float(search_radius_y),
        max_disparity=2.0 * float(search_radius), impl=impl)
    ok = (idx >= 0) & (dist <= cfg.max_hamming) & (prev.meta[..., 3] > 0.5)
    eff = jnp.where(ok, idx, 0)
    pts_curr = jnp.take_along_axis(curr.points, eff[..., None], axis=-2)
    ok_curr = jnp.take_along_axis(curr.valid, eff, axis=-1)
    w = (ok & prev.valid & ok_curr).astype(jnp.float32)
    return prev.points, pts_curr, w


def solve_pose(pts_prev: jnp.ndarray, pts_curr: jnp.ndarray,
               weights: jnp.ndarray, *, iters: int = 3,
               keep_frac: float = 0.7,
               min_corr: int = MIN_CORRESPONDENCES) -> PoseSet:
    """Robust weighted Procrustes for ONE rig: (N, 3) paired points +
    (N,) 0/1 weights -> ``PoseSet`` with ``p_curr = R @ p_prev + t``."""
    w0 = weights.astype(jnp.float32)
    # Insurance against upstream garbage (a corrupt slab that slipped
    # every mask): a non-finite correspondence never enters the solve.
    finite = (jnp.isfinite(pts_prev).all(axis=-1)
              & jnp.isfinite(pts_curr).all(axis=-1))
    w0 = jnp.where(finite, w0, 0.0)
    n0 = jnp.sum((w0 > 0).astype(jnp.int32))
    n_total = w0.shape[0]

    def round_(w_c, _):
        r_c, t_c = backend.kabsch(pts_prev, pts_curr, w_c)
        # f32 residuals: a default f32 matmul on the TPU runs in bf16
        # passes, which would reorder the inlier ranking.
        moved = jnp.matmul(pts_prev, r_c.T,
                           precision=jax.lax.Precision.HIGHEST)
        res = jnp.linalg.norm(moved + t_c - pts_curr, axis=-1)
        n = jnp.sum((w_c > 0).astype(jnp.int32))
        keep = jnp.maximum(jnp.int32(min_corr),
                           jnp.ceil(keep_frac * n).astype(jnp.int32))
        # masked top-K: threshold at the keep-th smallest residual of
        # the current support (static shape: sort with +inf fill), then
        # re-gate the FULL weight set so a point wrongly dropped in an
        # early round can re-enter once the pose estimate improves.
        filled = jnp.where(w_c > 0, res, jnp.inf)
        thr = jnp.sort(filled)[jnp.clip(keep - 1, 0, n_total - 1)]
        return jnp.where((res <= thr) & (w0 > 0), w0, 0.0), None

    w, _ = jax.lax.scan(round_, w0, None, length=iters)
    r, t = backend.kabsch(pts_prev, pts_curr, w)
    inliers = jnp.sum((w > 0).astype(jnp.int32))

    # Degeneracy gate: a collapsed support cloud (zero/near-zero
    # baseline puts every point at the origin) has no orientation
    # information — the SVD returns SOME orthogonal matrix, so the
    # spread check is what turns "finite but meaningless" into invalid.
    wn = w / jnp.maximum(jnp.sum(w), 1e-6)
    centered = pts_prev - jnp.sum(wn[:, None] * pts_prev, axis=0)
    spread = jnp.sum(wn * jnp.sum(centered * centered, axis=-1))
    ok = ((inliers >= min_corr) & (n0 >= min_corr) & (spread > 1e-8)
          & jnp.isfinite(r).all() & jnp.isfinite(t).all())
    r = jnp.where(ok, r, jnp.eye(3, dtype=jnp.float32))
    t = jnp.where(ok, t, jnp.zeros(3, dtype=jnp.float32))
    return PoseSet(rotation=r.astype(jnp.float32),
                   translation=t.astype(jnp.float32),
                   inliers=inliers, valid=ok)


def solve_pose_batched(pts_prev: jnp.ndarray, pts_curr: jnp.ndarray,
                       weights: jnp.ndarray, *, iters: int = 3,
                       keep_frac: float = 0.7,
                       min_corr: int = MIN_CORRESPONDENCES) -> PoseSet:
    """vmap of ``solve_pose`` over a leading batch axis: (B, N, 3) x 2
    + (B, N) -> ``PoseSet`` with (B,) leading axes.  B is rigs for a
    fleet frame, frame transitions for a sequence, or both folded."""
    solve = functools.partial(solve_pose, iters=iters,
                              keep_frac=keep_frac, min_corr=min_corr)
    return jax.vmap(solve)(pts_prev, pts_curr, weights)
