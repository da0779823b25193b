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


def _order_key(flat: jnp.ndarray) -> jnp.ndarray:
    """An integer key with the order and the equalities of ``flat``.
    Integers are their own key.  A float maps to the signed integer of
    its bits, the bits below the sign flipped for negatives, after
    ``-0.0`` becomes ``+0.0`` so that equal floats have equal keys."""
    if jnp.issubdtype(flat.dtype, jnp.integer):
        return flat
    itype = jnp.dtype(f"int{8 * flat.dtype.itemsize}")
    bits = jax.lax.bitcast_convert_type(
        jnp.where(flat == 0, jnp.zeros_like(flat), flat), itype)
    return jnp.where(bits < 0, bits ^ jnp.iinfo(itype).max, bits)


def _kth_largest(key: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest of ``key``: the largest t with
    ``sum(key >= t) >= k``, bisected between the smallest and the
    largest key, so the trip count follows the data's range (about 9
    for FAST scores in [0, 255])."""
    def step(bounds):
        lo, hi = bounds
        # ceil((lo + hi) / 2) without forming lo + hi, which can overflow.
        mid = (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)
        ok = jnp.sum((key >= mid).astype(jnp.int32)) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = jax.lax.while_loop(lambda b: b[0] < b[1], step,
                               (jnp.min(key), jnp.max(key)))
    return lo


def _nth_true(mask: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Flat position of the n-th (from 1) True of the 1-D ``mask`` for
    each entry of ``n``, which must not exceed the count of True.

    A search in two levels, so that no running count of the whole mask
    is formed: the mask is cut into rows of 128, a binary search of the
    running count of the row totals finds each n-th True's row, and a
    running count inside that row alone finds its column."""
    width = 128
    rows = -(-mask.shape[0] // width)
    grid = jnp.pad(mask, (0, rows * width - mask.shape[0])).reshape(
        rows, width)
    upto = jnp.cumsum(jnp.sum(grid, axis=1, dtype=jnp.int32))
    row = jnp.searchsorted(upto, n, method="scan")
    before = jnp.where(row > 0, upto[row - 1], 0)
    in_row = jnp.cumsum(grid[row].astype(jnp.int32), axis=-1)
    col = jnp.argmax(in_row >= (n - before)[..., None], axis=-1)
    return row * width + col.astype(row.dtype)


@jax.named_scope("select_topk")
def select_topk(score: jnp.ndarray, k: int, border: int):
    """Top-K corners of a score map. Returns (xy (K,2) int32, score (K,),
    valid (K,) bool), ordered by score descending, ties by the lower
    flat index first.

    No ``lax.top_k``: the TPU lowers it to a full sort of the map (four
    of them took three quarters of a 720p quad frame), and its chunked
    sorts see values only, so the order of equal scores (FAST scores
    are small integers: ties are common) changed with the batch size.
    Instead the K-th score is bisected by counting, the lowest-index
    corners tied at it are kept, the K kept corners are listed in index
    order by searching the running count of kept corners, and only
    those K are sorted.  Its device ops carry the ``select_topk``
    scope."""
    h, w = score.shape
    if not 0 < k <= h * w:
        raise ValueError(f"k={k} outside 1..{h * w}, the size of the map")
    row = jnp.arange(h)[:, None]
    col = jnp.arange(w)[None, :]
    inside = ((row >= border) & (row < h - border)
              & (col >= border) & (col < w - border))
    flat = jnp.where(inside, score, jnp.zeros_like(score)).reshape(-1)
    key = _order_key(flat)
    kth = _kth_largest(key, k)
    above = key > kth
    at = key == kth
    # Of the corners tied at the K-th score, the n_at lowest-index ones
    # are kept: those up to the n_at-th.
    n_at = k - jnp.sum(above.astype(jnp.int32))
    pos = jnp.arange(flat.shape[0], dtype=jnp.int32)
    keep = above | (at & (pos <= _nth_true(at, n_at)))
    idx = _nth_true(keep, jnp.arange(1, k + 1, dtype=jnp.int32))
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
