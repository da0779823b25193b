"""Oriented FAST detection (paper Sec. II-B1, III-C) — thin wrappers
over the two-stage kernel pipeline.

The frontend splits per pyramid level into a DENSE stage (fused
blur + FAST + NMS megakernel over every pixel) and a SPARSE stage (one
``ops.orient_describe_batched`` launch over the top-K keypoints).  This
module owns the pieces between them: static top-K selection, plus
single-image convenience wrappers that route through the SAME sparse
dispatch as the batched hot path, so single-image and batched results
are bit-identical.

The 31x31 patch geometry, circular-patch moment grids and the
orientation oracle live in ``kernels.ref`` (shared with the Pallas
kernel); the standalone 3x3 NMS oracle is re-exported here for
back-compat.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import ORBConfig
from repro.kernels import ops
from repro.kernels.ref import nms3  # noqa: F401  (oracle; back-compat export)
from repro.kernels.ref import PATCH, RADIUS  # noqa: F401


@jax.named_scope("select_topk")
def select_topk(score: jnp.ndarray, k: int, border: int):
    """Top-K corners of a score map. Returns (xy (K,2) int32, score (K,),
    valid (K,) bool), ordered by score descending, ties by the lower
    flat index first.

    ``lax.top_k`` promises that tie order, but the TPU lowers it to
    chunked sorts whose comparator sees values only, so equal scores
    (FAST scores are small integers: ties are common) came back in an
    order that changed with the batch size.  Here top-K only supplies
    the K-th value; which of the tied corners at that value are kept
    (the lowest indices), and the order of the K, are decided
    explicitly.  Its device ops carry the ``select_topk`` scope."""
    h, w = score.shape
    row = jnp.arange(h)[:, None]
    col = jnp.arange(w)[None, :]
    inside = ((row >= border) & (row < h - border)
              & (col >= border) & (col < w - border))
    flat = jnp.where(inside, score, jnp.zeros_like(score)).reshape(-1)
    kth = jax.lax.top_k(flat, k)[0][k - 1]
    above = flat > kth
    at = flat == kth
    n_at = k - jnp.sum(above.astype(jnp.int32))
    keep = above | (at & (jnp.cumsum(at.astype(jnp.int32)) <= n_at))
    # Exactly k corners are kept; their negated flat indices are unique
    # keys, so this top-K is tie-free and returns them by index.
    pos = jnp.arange(flat.shape[0], dtype=jnp.int32)
    idx = -jax.lax.top_k(jnp.where(keep, -pos, jnp.iinfo(jnp.int32).min),
                         k)[0]
    neg_vals, idx = jax.lax.sort((-flat[idx], idx), num_keys=2)
    vals = -neg_vals
    ys = idx // w
    xs = idx % w
    valid = vals > 0
    return jnp.stack([xs, ys], axis=-1), vals, valid


def orientations(img: jnp.ndarray, xy: jnp.ndarray,
                 impl: str | None = None) -> jnp.ndarray:
    """Intensity-centroid orientation theta = atan2(m01, m10) (paper
    Eq. 1) for a single image — batch-of-one view of the fused sparse
    dispatch (orientation-only kernel), so it shares every bit with
    ``orb.extract_features_batched``.

    img: (H, W) float32 level image; xy: (K, 2) int32.  Coordinates are
    clamped into the image by the dispatch.
    """
    theta, _, _ = ops.orient_describe_batched(img[None], None, xy[None],
                                              impl=impl)
    return theta[0]


def detect(level_img: jnp.ndarray, cfg: ORBConfig, k: int,
           impl: str | None = None):
    """Run oriented FAST on one pyramid level (single-image path).

    Score-only dispatch: the standalone FAST kernel plus the ``nms3``
    oracle — bit-identical to the fused megakernel's score output (the
    kernels differ only in min/max association, which is exact) without
    computing the blur this path would discard (a pallas_call output
    cannot be dead-code-eliminated).  Orientation then routes through
    the SAME ``ops.orient_describe_batched`` dispatch as the batched hot
    path (orientation-only kernel: no smoothed image, no descriptor), so
    ``detect`` and ``orb.extract_features_batched`` can never diverge on
    theta.  The frontend hot path uses ``orb.extract_features_batched``
    instead.

    Returns (xy (K,2) int32 level coords, score (K,), theta (K,),
    valid (K,))."""
    score = ops.fast_score_map(level_img, float(cfg.fast_threshold),
                               impl=impl)
    if cfg.nms:
        score = nms3(score)
    xy, vals, valid = select_topk(score, k, cfg.border)
    theta = orientations(level_img, xy, impl=impl)
    return xy, vals, theta, valid
