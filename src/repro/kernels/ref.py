"""Pure-jnp oracles for every Pallas kernel.

These are the ground-truth implementations: numerically straightforward,
shape-polymorphic, no tiling.  ``ops.py`` dispatches between these and
the Pallas kernels; tests assert exact/allclose agreement on shape and
dtype sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import pattern

# Bresenham circle of radius 3 — the 16 FAST taps, in order around the
# circle, as (dx, dy) with y down.  (paper Sec. II-B1)
CIRCLE16: tuple[tuple[int, int], ...] = (
    (0, -3), (1, -3), (2, -2), (3, -1), (3, 0), (3, 1), (2, 2), (1, 3),
    (0, 3), (-1, 3), (-2, 2), (-3, 1), (-3, 0), (-3, -1), (-2, -2), (-1, -3),
)
ARC_LEN = 9  # FAST-9/16: a corner needs >= 9 contiguous bright/dark taps


def fast_score_map(img: jnp.ndarray, threshold: float) -> jnp.ndarray:
    """FAST-9/16 corner score map.

    score(p) = max(max_s min_{j<9} d[s+j], -min_s max_{j<9} d[s+j]) where
    d[i] = I(circle_i) - I(p); a pixel is a corner iff score > threshold.
    Returns float32 (H, W); 0 where not a corner.  Border pixels (3 px)
    use edge padding and are masked downstream by the feature border.
    """
    img = img.astype(jnp.float32)
    h, w = img.shape
    pad = jnp.pad(img, 3, mode="edge")
    taps = [
        jax.lax.dynamic_slice(pad, (3 + dy, 3 + dx), (h, w)) - img
        for dx, dy in CIRCLE16
    ]
    d = jnp.stack(taps)                        # (16, H, W)
    dd = jnp.concatenate([d, d[: ARC_LEN - 1]], axis=0)   # wrap for arcs
    bright = jnp.stack(
        [jnp.min(dd[s : s + ARC_LEN], axis=0) for s in range(16)]
    )                                           # (16, H, W) min over each arc
    dark = jnp.stack(
        [jnp.max(dd[s : s + ARC_LEN], axis=0) for s in range(16)]
    )
    score = jnp.maximum(jnp.max(bright, axis=0), -jnp.min(dark, axis=0))
    return jnp.where(score > threshold, score, 0.0).astype(jnp.float32)


def nms3(score: jnp.ndarray) -> jnp.ndarray:
    """3x3 non-max suppression: keep pixels that are the strict max of
    their neighbourhood (score >= all 8 neighbours, and positive).

    Neighbours outside the image are -1.0 (constant pad), so border
    pixels compete only against real pixels.  This is the oracle for the
    NMS stage fused into ``frontend_fused.py``; the frontend hot path no
    longer runs these eight host-graph dynamic slices.
    """
    h, w = score.shape
    pad = jnp.pad(score, 1, mode="constant",
                  constant_values=jnp.asarray(-1, score.dtype))
    neigh = []
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            if dy == 0 and dx == 0:
                continue
            neigh.append(jax.lax.dynamic_slice(pad, (1 + dy, 1 + dx), (h, w)))
    nmax = functools.reduce(jnp.maximum, neigh)
    keep = jnp.where(score >= nmax, score, jnp.zeros_like(score))
    return keep * (score > 0).astype(score.dtype)


def fast_blur_nms(img: jnp.ndarray, threshold: float, *, nms: bool = True,
                  quantized: bool = True):
    """Single-image oracle for the fused frontend megakernel.

    Returns (blur, score): the 7x7-Gaussian-smoothed image and the
    (optionally NMS'd) FAST-9/16 score map, exactly the two outputs
    ``frontend_fused_pallas`` emits per batch slice.
    """
    blur = gaussian_blur7(img, quantized=quantized)
    score = fast_score_map(img, threshold)
    if nms:
        score = nms3(score)
    return blur, score


# 7x7 Gaussian (sigma=2) with integer weights — the word-length-optimized
# filter of paper Sec. III-C.  Integer taps keep the quantized path exact.
GAUSS7_WEIGHTS_INT = np.array([1, 4, 8, 10, 8, 4, 1], dtype=np.int32)
GAUSS7_NORM = int(GAUSS7_WEIGHTS_INT.sum())  # 36


def gaussian_blur7(img: jnp.ndarray, quantized: bool = True) -> jnp.ndarray:
    """Separable 7x7 Gaussian smoothing (paper's Image Smoothing module).

    quantized=True reproduces the 8-bit datapath: integer taps, integer
    accumulate, single rounding division at the end (exactly computable
    in int32, so the Pallas kernel can match bit-for-bit).
    """
    w = jnp.asarray(GAUSS7_WEIGHTS_INT, dtype=jnp.float32)
    img_f = img.astype(jnp.float32)
    pad = jnp.pad(img_f, 3, mode="edge")
    h, wid = img.shape
    # Horizontal then vertical pass, as two explicit tap sums (streaming
    # line-buffer analog; avoids conv_general_dilated for interpret parity).
    horiz = sum(
        w[k] * jax.lax.dynamic_slice(pad, (3, k), (h + 6, wid))
        for k in range(7)
    )                                             # (H+6, W), weight-summed x
    vert = sum(
        w[k] * jax.lax.dynamic_slice(horiz, (k, 0), (h, wid))
        for k in range(7)
    )                                             # (H, W)
    if quantized:
        # round-half-up of vert / norm^2, all-integer equivalent
        return jnp.floor((vert + (GAUSS7_NORM * GAUSS7_NORM) / 2.0)
                         / (GAUSS7_NORM * GAUSS7_NORM)).astype(jnp.float32)
    return vert / float(GAUSS7_NORM * GAUSS7_NORM)


# ---------------------------------------------------------------------------
# Integer-datapath oracles (paper Sec. III word-length optimization).
#
# The uint8 pipeline holds pyramid slabs as uint8 and runs blur / FAST /
# NMS / moments on integer accumulators.  Each oracle below states why
# its output is BIT-EQUAL to the f32 oracle on quantized (integer-
# valued) images; tests pin that equivalence on ref and
# pallas-interpret.

def int_threshold(threshold: float) -> int:
    """FAST threshold for the integer datapath.  For integer scores,
    ``score > threshold`` == ``score > floor(threshold)`` exactly, so
    the int16 compare reproduces the f32 compare bit-for-bit."""
    return int(np.floor(threshold))


def fast_score_map_int(img: jnp.ndarray, threshold: float) -> jnp.ndarray:
    """Integer FAST-9/16 oracle: uint8 image -> int16 score map.

    Taps d = I(circle) - I(p) live in [-255, 255]; arc min/max and the
    final max stay in that range, so int16 is exact and equals the f32
    oracle's values on integer images.
    """
    img_i = img.astype(jnp.int32)
    h, w = img.shape
    pad = jnp.pad(img_i, 3, mode="edge")
    taps = [
        jax.lax.dynamic_slice(pad, (3 + dy, 3 + dx), (h, w)) - img_i
        for dx, dy in CIRCLE16
    ]
    d = jnp.stack(taps)
    dd = jnp.concatenate([d, d[: ARC_LEN - 1]], axis=0)
    bright = jnp.stack(
        [jnp.min(dd[s: s + ARC_LEN], axis=0) for s in range(16)]
    )
    dark = jnp.stack(
        [jnp.max(dd[s: s + ARC_LEN], axis=0) for s in range(16)]
    )
    score = jnp.maximum(jnp.max(bright, axis=0), -jnp.min(dark, axis=0))
    thr = jnp.int32(int_threshold(threshold))
    return jnp.where(score > thr, score, 0).astype(jnp.int16)


def gaussian_blur7_u8(img: jnp.ndarray) -> jnp.ndarray:
    """Integer-datapath 7x7 Gaussian: uint8 -> uint8.

    int32 accumulate + round-half-up integer division.  vert + 648 <=
    255*36*36 + 648 = 331128 < 2^24, so the f32 oracle's
    ``floor((vert + 648.0) / 1296.0)`` computes the same quotient: the
    int32 path is bit-equal to ``gaussian_blur7(img, quantized=True)``.
    """
    w = jnp.asarray(GAUSS7_WEIGHTS_INT, dtype=jnp.int32)
    pad = jnp.pad(img.astype(jnp.int32), 3, mode="edge")
    h, wid = img.shape
    horiz = sum(
        w[k] * jax.lax.dynamic_slice(pad, (3, k), (h + 6, wid))
        for k in range(7)
    )
    vert = sum(
        w[k] * jax.lax.dynamic_slice(horiz, (k, 0), (h, wid))
        for k in range(7)
    )
    norm2 = GAUSS7_NORM * GAUSS7_NORM
    return ((vert + norm2 // 2) // norm2).astype(jnp.uint8)


def fast_blur_nms_int(img: jnp.ndarray, threshold: float, *,
                      nms: bool = True):
    """uint8 single-image oracle for the fused frontend: returns
    (blur uint8, score int16) — the integer twins of ``fast_blur_nms``'s
    outputs, equal in value on quantized images."""
    blur = gaussian_blur7_u8(img)
    score = fast_score_map_int(img, threshold)
    if nms:
        score = nms3(score)
    return blur, score


def _popcount32(x: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount of a uint32 array -> int32 (no native popcount on VPU)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def hamming_distance_matrix(desc_l: jnp.ndarray,
                            desc_r: jnp.ndarray) -> jnp.ndarray:
    """(K, 8) x (M, 8) uint32 descriptors -> (K, M) int32 Hamming distances."""
    x = jnp.bitwise_xor(desc_l[:, None, :], desc_r[None, :, :])
    return jnp.sum(_popcount32(x), axis=-1)


# ---------------------------------------------------------------------------
# 31x31 patch oracles — the sparse descriptor stage (orientation + rBRIEF).
#
# These are the single definition of the edge-pad + patch-slice geometry
# that used to be copy-pasted between ``fast.orientations`` and
# ``brief.describe``; both core wrappers and the fused Pallas kernel
# (``describe_fused.py``) build on them.

PATCH = 2 * pattern.PATCH_RADIUS + 1      # 31
RADIUS = pattern.PATCH_RADIUS             # 15


def pad_patch(img: jnp.ndarray) -> jnp.ndarray:
    """Edge-pad by RADIUS so a 31x31 slice starting at (y, x) of the
    padded image is the patch *centered* on pixel (x, y)."""
    return jnp.pad(img.astype(jnp.float32), RADIUS, mode="edge")


def extract_patches(img: jnp.ndarray, xy: jnp.ndarray, *,
                    preserve_dtype: bool = False) -> jnp.ndarray:
    """(H, W) image + (K, 2) int32 centers -> (K, 31, 31) patches.

    Centers are clamped into the image (top-K padding rows may carry
    arbitrary coordinates) — identical clamping to the Pallas kernel.
    This is the host-graph gather the fused kernel replaces; kept as the
    oracle and the single-image fallback.  ``preserve_dtype=True`` keeps
    the input dtype (the uint8 datapath); default casts to f32 as the
    f32 oracle always did.
    """
    padded = (jnp.pad(img, RADIUS, mode="edge") if preserve_dtype
              else pad_patch(img))
    h, w = img.shape

    def one(pt):
        x = jnp.clip(pt[0], 0, w - 1)
        y = jnp.clip(pt[1], 0, h - 1)
        return jax.lax.dynamic_slice(padded, (y, x), (PATCH, PATCH))

    return jax.vmap(one)(xy)


def moment_grids():
    """The circular-mask moment grids (X_GRID, Y_GRID) built from 2D
    iota instead of baked numpy constants — bit-identical values (small
    integers are exact in f32), but legal inside a Pallas kernel body,
    where captured array constants are rejected."""
    yy = (jax.lax.broadcasted_iota(jnp.float32, (PATCH, PATCH), 0)
          - float(RADIUS))
    xx = (jax.lax.broadcasted_iota(jnp.float32, (PATCH, PATCH), 1)
          - float(RADIUS))
    mask = (xx * xx + yy * yy <= float(RADIUS * RADIUS)).astype(jnp.float32)
    return xx * mask, yy * mask


def patch_theta(patches: jnp.ndarray):
    """(..., 31, 31) raw patches -> (theta (...,), moments (..., 2)).

    Intensity-centroid moments over the circular patch (paper Eq. 1):
    m10 = sum(x * I), m01 = sum(y * I), theta = atan2(m01, m10).  Shared
    verbatim by the ref oracle, the jnp fallback and the Pallas kernel
    body so all three are bit-identical.
    """
    xg, yg = moment_grids()
    m10 = jnp.sum(patches * xg, axis=(-2, -1))
    m01 = jnp.sum(patches * yg, axis=(-2, -1))
    return jnp.arctan2(m01, m10), jnp.stack([m10, m01], axis=-1)


def moment_grids_int():
    """Integer twins of ``moment_grids``: int32 circular-mask coordinate
    grids for the uint8 datapath's int32 moment accumulators."""
    yy = (jax.lax.broadcasted_iota(jnp.int32, (PATCH, PATCH), 0)
          - RADIUS)
    xx = (jax.lax.broadcasted_iota(jnp.int32, (PATCH, PATCH), 1)
          - RADIUS)
    mask = (xx * xx + yy * yy <= RADIUS * RADIUS).astype(jnp.int32)
    return xx * mask, yy * mask


def patch_theta_int(patches: jnp.ndarray):
    """uint8 (..., 31, 31) patches -> (theta (...,) f32, moments
    (..., 2) int32), int32 accumulators.

    |m10|, |m01| <= 255 * sum|x| over the circular mask ~ 1.4e6 < 2^24,
    so the f32 oracle's moment sums are exact and the int32 moments
    equal them; theta = atan2 of the same two f32 values is bit-equal.
    """
    xg, yg = moment_grids_int()
    p = patches.astype(jnp.int32)
    m10 = jnp.sum(p * xg, axis=(-2, -1))
    m01 = jnp.sum(p * yg, axis=(-2, -1))
    theta = jnp.arctan2(m01.astype(jnp.float32), m10.astype(jnp.float32))
    return theta, jnp.stack([m10, m01], axis=-1)


def orient_describe_int(raw: jnp.ndarray, smoothed: jnp.ndarray,
                        xy: jnp.ndarray):
    """uint8 single-image oracle for the fused sparse stage.

    raw/smoothed: (H, W) uint8 level image + its uint8 blur; xy: (K, 2)
    int32.  Returns (theta f32, moments int32 (K, 2), desc uint32
    (K, 8)).  Theta is bit-equal to the f32 oracle (see
    ``patch_theta_int``); descriptors compare the same integer tap
    values, so they are bit-equal too.
    """
    theta, mom = patch_theta_int(
        extract_patches(raw, xy, preserve_dtype=True))
    desc = lut_descriptor(
        extract_patches(smoothed, xy, preserve_dtype=True),
        theta_to_bin(theta))
    return theta, mom, desc


# theta -> steering bin: nearest bin center, bins at b * ANGLE_BIN_STEP.
_INV_ANGLE_STEP = float(pattern.N_ANGLE_BINS / (2.0 * np.pi))


def theta_to_bin(theta: jnp.ndarray) -> jnp.ndarray:
    """(...,) float32 theta in (-pi, pi] -> (...,) int32 bin in [0, 12)."""
    return jnp.mod(jnp.round(theta * _INV_ANGLE_STEP).astype(jnp.int32),
                   pattern.N_ANGLE_BINS)


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., 256) bool -> (..., 8) uint32, bit i of word i // 32.

    The paper's 32 x 8-bit descriptor RAM layout.  Bitwise-disjoint
    uint32 adds, so any summation order is exact.
    """
    w = bits.astype(jnp.uint32).reshape(*bits.shape[:-1], 8, 32)
    weights = (jnp.uint32(1)
               << jax.lax.broadcasted_iota(jnp.uint32, (8, 32), 1))
    return jnp.sum(w * weights, axis=-1)


def lut_descriptor(sm_patches: jnp.ndarray,
                   bins: jnp.ndarray) -> jnp.ndarray:
    """(K, 31, 31) smoothed patches + (K,) int32 steering bins ->
    (K, 8) uint32 rBRIEF descriptors (gather oracle).

    Taps are resolved through ``pattern.STEER_LUT`` — the same ROM the
    Pallas kernel reads; the kernel differs only in resolving taps with
    a one-hot matmul instead of this gather, which cannot change any bit
    (tau = p(A) < p(B) iff fl(p(B) - p(A)) > 0 exactly in f32).
    """
    lut = jnp.asarray(pattern.STEER_LUT)                 # (12, 256, 2)
    idx = lut[bins]                                      # (K, 256, 2)
    flat = sm_patches.reshape(-1, PATCH * PATCH)
    pa = jnp.take_along_axis(flat, idx[..., 0], axis=1)
    pb = jnp.take_along_axis(flat, idx[..., 1], axis=1)
    return pack_bits(pa < pb)                            # paper Eq. 2


def orient_describe(raw: jnp.ndarray, smoothed: jnp.ndarray,
                    xy: jnp.ndarray):
    """Single-image oracle for the fused sparse stage.

    raw/smoothed: (H, W) float32 level image and its 7x7-Gaussian blur;
    xy: (K, 2) int32 level coords.  Returns (theta (K,), moments (K, 2),
    desc (K, 8) uint32) — exactly the three outputs
    ``ops.orient_describe_batched`` returns for one image.
    """
    theta, mom = patch_theta(extract_patches(raw, xy))
    desc = lut_descriptor(extract_patches(smoothed, xy),
                          theta_to_bin(theta))
    return theta, mom, desc


def steered_offsets(theta: jnp.ndarray):
    """EXACT pattern steering for one angle (paper Eq. 3): per-angle
    cos/sin + round.  Returns int32 (N, 2) offsets for A and B points.

    Superseded in the pipeline by the binned ``pattern.STEER_LUT``; kept
    as the reference the bin quantization is measured against (and the
    pre-refactor descriptor definition).
    """
    c, s = jnp.cos(theta), jnp.sin(theta)
    pa = jnp.asarray(pattern.PATTERN_A, dtype=jnp.float32)
    pb = jnp.asarray(pattern.PATTERN_B, dtype=jnp.float32)

    def rot(p):
        x = c * p[:, 0] - s * p[:, 1]
        y = s * p[:, 0] + c * p[:, 1]
        return jnp.stack([jnp.round(x), jnp.round(y)], axis=-1).astype(
            jnp.int32)

    return rot(pa), rot(pb)


def describe_steered(smoothed: jnp.ndarray, xy: jnp.ndarray,
                     theta: jnp.ndarray) -> jnp.ndarray:
    """Pre-refactor EXACT-steering rBRIEF oracle: (K, 8) uint32.

    Rotates all 256 pairs by each keypoint's exact theta.  The pipeline
    now uses the binned LUT instead; descriptor differences between the
    two are bounded by the 30-degree bin quantization (pinned in tests).
    """
    patches = extract_patches(smoothed, xy)

    def one(patch, th):
        a, b = steered_offsets(th)
        pa = patch[a[:, 1] + RADIUS, a[:, 0] + RADIUS]
        pb = patch[b[:, 1] + RADIUS, b[:, 0] + RADIUS]
        return pack_bits(pa < pb)

    return jax.vmap(one)(patches, theta)


def gather_patches(img: jnp.ndarray, xy: jnp.ndarray, ph: int, pw: int):
    """Gather (ph, pw) patches centered at rounded xy from one image.

    The FM stage's patch-read semantics, in ONE place: centers are
    rounded (round-half-even) and clamped into the image, and window
    pixels overhanging the border replicate the edge (``jnp.pad
    mode="edge")``.  This host-graph gather is the oracle the fused
    matcher kernels' in-kernel slab reads are pinned against
    (``matcher_fused.py`` clamps identically), and the jnp fallback of
    ``ops.sad_patch_search``; ``matching._gather_patches`` is a thin
    alias.  img: (H, W); xy: (K, 2) float32."""
    ry, rx = ph // 2, pw // 2
    padded = jnp.pad(img.astype(jnp.float32), ((ry, ry), (rx, rx)),
                     mode="edge")
    xs = jnp.clip(jnp.round(xy[:, 0]).astype(jnp.int32), 0,
                  img.shape[1] - 1)
    ys = jnp.clip(jnp.round(xy[:, 1]).astype(jnp.int32), 0,
                  img.shape[0] - 1)

    def one(x, y):
        return jax.lax.dynamic_slice(padded, (y, x), (ph, pw))

    return jax.vmap(one)(xs, ys)


# ---------------------------------------------------------------------------
# Brute-force NUMPY oracles for the matcher ops — python loops, no jnp,
# no vectorization tricks.  These are deliberately the dumbest possible
# implementations: the jnp oracles above and the Pallas kernels are both
# pinned against them in tests, so a vectorization bug cannot hide in a
# shared formulation.

MATCH_BIG = 1 << 20       # no-candidate sentinel; == hamming_match.BIG


def gather_patches_bruteforce(img, xy, ph: int, pw: int):
    """Python-loop reference of ``gather_patches``: per-PIXEL coordinate
    clamping instead of pad-then-slice, so a border off-by-one in the
    pad/slice formulation cannot hide.  For a center clamped to (xc, yc)
    the window pixel (dy, dx) is img[clip(yc + dy - ph//2, 0, H - 1),
    clip(xc + dx - pw//2, 0, W - 1)] — edge replication IS per-axis
    clamping.  img: (H, W); xy: (K, 2) float; returns (K, ph, pw) f32."""
    img = np.asarray(img, dtype=np.float32)
    xy = np.asarray(xy, dtype=np.float32)
    h, w = img.shape
    ry, rx = ph // 2, pw // 2
    out = np.zeros((xy.shape[0], ph, pw), np.float32)
    for i, (x, y) in enumerate(xy):
        xc = int(np.clip(np.round(x), 0, w - 1))
        yc = int(np.clip(np.round(y), 0, h - 1))
        for dy in range(ph):
            for dx in range(pw):
                out[i, dy, dx] = img[min(max(yc + dy - ry, 0), h - 1),
                                     min(max(xc + dx - rx, 0), w - 1)]
    return out


def hamming_match_bruteforce(desc_l, meta_l, desc_r, meta_r,
                             row_band: float, max_disparity: float):
    """O(K*M) python-loop reference of the fused search-region + Hamming
    argmin (``ops.hamming_match``).

    desc_*: (K, 8) uint32; meta_*: (K, 4) float32 (x, y, level, valid).
    Returns numpy (dist (K,) int32 [MATCH_BIG when no candidate], idx
    (K,) int32 [-1]).  Ties resolve to the LOWEST right index, matching
    jnp argmin.
    """
    desc_l = np.asarray(desc_l, dtype=np.uint32)
    desc_r = np.asarray(desc_r, dtype=np.uint32)
    meta_l = np.asarray(meta_l, dtype=np.float32)
    meta_r = np.asarray(meta_r, dtype=np.float32)
    kl, kr = desc_l.shape[0], desc_r.shape[0]
    dist = np.full(kl, MATCH_BIG, np.int32)
    idx = np.full(kl, -1, np.int32)
    for i in range(kl):
        if meta_l[i, 3] <= 0.5:
            continue
        best, best_j = MATCH_BIG, -1
        for j in range(kr):
            if meta_r[j, 3] <= 0.5:
                continue
            dx = meta_l[i, 0] - meta_r[j, 0]
            dy = abs(meta_l[i, 1] - meta_r[j, 1])
            if not (dy <= row_band and 0.0 <= dx <= max_disparity
                    and meta_l[i, 2] == meta_r[j, 2]):
                continue
            d = sum(bin(int(a) ^ int(b)).count("1")
                    for a, b in zip(desc_l[i], desc_r[j]))
            if d < best:
                best, best_j = d, j
        dist[i], idx[i] = best, best_j
    return dist, idx


def sad_search_bruteforce(left_patches, right_strips):
    """Python-loop reference of the SAD sweep (``ops.sad_search``):
    (K, P, P) x (K, P, P+2R) -> (K, 2R+1) int32."""
    lp = np.asarray(left_patches).astype(np.int64)
    rs = np.asarray(right_strips).astype(np.int64)
    k, p, _ = lp.shape
    sweep = rs.shape[-1] - p + 1
    table = np.zeros((k, sweep), np.int64)
    for i in range(k):
        for s in range(sweep):
            table[i, s] = np.abs(lp[i] - rs[i, :, s:s + p]).sum()
    return table.astype(np.int32)


# ---------------------------------------------------------------------------
# Bounded-error comparators — the uint8-vs-f32 correctness contract.
# Where the integer math is exact (blur, FAST, moments, descriptors on
# quantized images) tests pin bit-equality; everywhere else (float
# inputs snapped to uint8, wire quantization) they pin a measured bound
# through these helpers.

def max_abs_err(a, b) -> float:
    """max |a - b| in f32 — the bound the wire/quantization pins use."""
    a = jnp.asarray(a).astype(jnp.float32)
    b = jnp.asarray(b).astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b))) if a.size else 0.0


def keypoint_set_diff(xy_a, valid_a, xy_b, valid_b) -> int:
    """Symmetric-difference size of two keypoint sets (valid (x, y)
    rows as python sets — top-K ordering and tie permutations between
    equal-score corners don't count as disagreement)."""
    def to_set(xy, valid):
        xy = np.asarray(xy).reshape(-1, np.asarray(xy).shape[-1])
        valid = np.asarray(valid).reshape(-1)
        return {tuple(map(float, r)) for r, v in zip(xy, valid) if v}
    return len(to_set(xy_a, valid_a) ^ to_set(xy_b, valid_b))


def descriptor_hamming_stats(desc, ref_desc, valid=None):
    """Per-descriptor Hamming distance between two (..., 8) uint32
    descriptor sets -> (mean, max) over valid rows; (0.0, 0) when
    nothing is valid.  The uint8-path pin: 0 bits where descriptors are
    exact-in-integers, a measured bound elsewhere."""
    d = np.asarray(jnp.sum(_popcount32(
        jnp.bitwise_xor(jnp.asarray(desc), jnp.asarray(ref_desc))), -1))
    if valid is not None:
        d = d[np.asarray(valid)]
    if d.size == 0:
        return 0.0, 0
    return float(d.mean()), int(d.max())


def sad_search(left_patches: jnp.ndarray,
               right_strips: jnp.ndarray) -> jnp.ndarray:
    """SAD rectification sweep (paper Sec. II-C2 / III-D).

    left_patches: (K, P, P) — window around each left feature.
    right_strips: (K, P, P + 2R) — horizontal strip around the matched
      right feature.
    Returns (K, 2R + 1) int32 SAD values; caller argmins to re-locate F'.
    """
    k, p, _ = left_patches.shape
    sweep = right_strips.shape[-1] - p + 1
    lp = left_patches.astype(jnp.int32)
    rs = right_strips.astype(jnp.int32)
    sads = [
        jnp.sum(jnp.abs(lp - jax.lax.dynamic_slice_in_dim(rs, s, p, axis=2)),
                axis=(1, 2))
        for s in range(sweep)
    ]
    return jnp.stack(sads, axis=1)
