"""Pallas TPU kernel: batched sparse ORB descriptor stage (orientation +
rBRIEF) — the gather-free half of the two-stage frontend.

The dense stage (``frontend_fused.py``) emits per-pixel products (blur +
NMS'd FAST score); after top-K the frontend needs three per-KEYPOINT
products: the intensity-centroid orientation theta (paper Eq. 1), the
circular-patch moments (m10, m01) and the packed 8 x uint32 rBRIEF
descriptor (paper Eqs. 2-3).  ``describe_fused_pyramid_pallas`` computes
them for ALL cameras x ALL pyramid levels in ONE launch: keypoint blocks
are level-sorted, each (camera, K-block) grid step resolves its
raw/smoothed slab pair through the static block->level offsets baked
into the index maps, and the clamp bounds come from a per-block
(true_h, true_w) shape table.  The slabs stay resident in VMEM (the
index maps pin them per (camera, level), so the pipeline fetches each
slab once, not once per K-block).  This mirrors the paper's FPGA
datapath (Sec. III-C), where a shared patch register bank feeds the
rotation and descriptor pipelines and the 31x31 window is read from BRAM
exactly once per feature.

Mosaic layout rules shape the kernel:

  * keypoint coordinates and the shape table are scalar-prefetched into
    SMEM (they are patch-start addresses, not vector data);
  * a dynamic load must start on an (8, 128) tile boundary, so each
    31x31 patch is read as the aligned (40, 256) window that contains
    it and rotated into place (``pltpu.roll``) before a static slice;
  * every output block spans its full minor dimension.

Steering is LUT-binned as in the paper: theta is quantized to 12 bins of
30 degrees and the rotated pattern comes from the ``pattern.STEER_LUT``
ROM.  Taps are resolved GATHER-FREE with one-hot matmuls on the MXU
(column select, then a masked row sum), so ``tau = p(A) < p(B)`` becomes
the sign of an exactly computed difference — BIT-exact against the
gather oracle (``ref.lut_descriptor``).  How many MXU passes that takes
follows the slab dtype:

  * integer slabs (the uint8 datapath) hold integers 0-255, exact in
    bf16's 8-bit significand (every integer up to 256 is), and the
    one-hot's 0/1 are exact too; each tap output has ONE nonzero product
    (a pixel times 1), the rest are zeros, so a single bf16 pass with
    f32 accumulation returns the pixel exactly;
  * float slabs hold arbitrary f32 values, which bf16 would round: their
    taps stay f32 ``Precision.HIGHEST`` (six bf16 passes, exact);
  * the bit pack multiplies 0/1 bits by distinct powers of two below
    2^16 — exact in bf16 — and their sum is exact in f32 in any order,
    so it takes one bf16 pass for every slab dtype.

Mosaic has no ``atan2``.  The kernel therefore does not decide the bin:
it computes the moments exactly (int32 for uint8 slabs; f32 sums of
integer products otherwise) and, from a polynomial angle estimate, the
two candidate bins round(t -+ BIN_MARGIN) (t = theta in bin units), and
emits the descriptor for both.  Unless the estimate is within
BIN_MARGIN of a bin edge the two candidates coincide.  The caller
(``ops``) computes theta = atan2(m01, m10) with XLA — the oracle's own
operation — and keeps the descriptor of the candidate equal to the
oracle's bin, so theta, moments and descriptors stay bit-exact.

Boundary semantics: keypoint coords are clamped into the true image
(top-K padding rows carry arbitrary coords) and the images are
edge-padded by RADIUS, exactly like ``ref.extract_patches``; the
alignment pad that ``ops.py`` adds is never read into a patch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pattern
from repro.kernels.ref import PATCH

KP_BLOCK = 8            # keypoints per grid step (a rolled in-kernel loop)
WIN_H = 40              # aligned patch window: 31 rows + 7 sublane shift
WIN_W = 256             # 31 lanes + up to 127 lane shift
BIN_MARGIN = 0.05       # candidate window (bins) around the angle estimate

_N_PAIRS = pattern.N_PAIRS
_N_BINS = pattern.N_ANGLE_BINS
_BINS_PER_RAD = _N_BINS / (2.0 * math.pi)


def steer_planes() -> np.ndarray:
    """``pattern.STEER_LUT`` as (4, 12, 256) int32 coordinate planes
    (y_a, x_a, y_b, x_b) of each pair's taps in the 31x31 patch."""
    lut = pattern.STEER_LUT
    return np.stack([lut[..., 0] // PATCH, lut[..., 0] % PATCH,
                     lut[..., 1] // PATCH, lut[..., 1] % PATCH]
                    ).astype(np.int32)


def pack_weights() -> np.ndarray:
    """(256, 16) bf16: bits @ W gives the low (cols 0-7) and high (cols
    8-15) 16-bit halves of the 8 descriptor words — each weight a power
    of two below 2^16 (exact in bf16), each half a sum of distinct ones
    (exact in f32)."""
    p = np.arange(_N_PAIRS)
    w = np.zeros((_N_PAIRS, 16), np.float32)
    lo = p % 32 < 16
    w[p[lo], p[lo] // 32] = 2.0 ** (p[lo] % 32)
    w[p[~lo], 8 + p[~lo] // 32] = 2.0 ** (p[~lo] % 32 - 16)
    return w.astype(jnp.bfloat16)


def single_pass_taps(dtype) -> bool:
    """Whether the kernel resolves the taps of slabs of ``dtype`` in one
    bf16 MXU pass: integer slabs (0-255) are exact in bf16, float slabs
    are not and keep f32 ``Precision.HIGHEST``."""
    return bool(jnp.issubdtype(dtype, jnp.integer))


def aligned_window(img_ref, y, x, rows: int, cols: int, win_h: int,
                   win_w: int):
    """The (rows, cols) window at dynamic start (y, x) of a 2-D VMEM
    ref: load the tile-aligned (win_h, win_w) window containing it and
    rotate it into place.  Integer slabs come back as int32."""
    y0 = pl.multiple_of((y // 8) * 8, 8)
    x0 = pl.multiple_of((x // 128) * 128, 128)
    win = img_ref[pl.ds(y0, win_h), pl.ds(x0, win_w)]
    if jnp.issubdtype(win.dtype, jnp.integer):
        win = win.astype(jnp.int32)
    win = pltpu.roll(win, (win_h - (y - y0)) % win_h, 0)
    win = pltpu.roll(win, (win_w - (x - x0)) % win_w, 1)
    return win[:rows, :cols]


def _moments(raw):
    """(31, 31) patch -> (m10, m01), each (1, 1), as in
    ``ref.patch_theta``/``ref.patch_theta_int``: int32 accumulators for
    integer patches, f32 otherwise."""
    integer = jnp.issubdtype(raw.dtype, jnp.integer)
    dt = jnp.int32 if integer else jnp.float32
    r = PATCH // 2
    yy = jax.lax.broadcasted_iota(jnp.int32, (PATCH, PATCH), 0) - r
    xx = jax.lax.broadcasted_iota(jnp.int32, (PATCH, PATCH), 1) - r
    mask = (xx * xx + yy * yy <= r * r).astype(jnp.int32)
    xg = (xx * mask).astype(dt)
    yg = (yy * mask).astype(dt)
    m10 = jnp.sum(raw * xg, axis=(0, 1), keepdims=True)
    m01 = jnp.sum(raw * yg, axis=(0, 1), keepdims=True)
    return m10.astype(jnp.float32), m01.astype(jnp.float32)


def approx_atan2(y, x):
    """atan2 to ~1e-5 rad from a minimax atan polynomial on [0, 1]
    (atan2(0, 0) = 0).  Used only to pick candidate steering bins."""
    ax, ay = jnp.abs(x), jnp.abs(y)
    hi = jnp.maximum(ax, ay)
    lo = jnp.minimum(ax, ay)
    z = lo / jnp.where(hi > 0, hi, 1.0)
    z2 = z * z
    a = z * (0.99997726 + z2 * (-0.33262347 + z2 * (
        0.19354346 + z2 * (-0.11643287 + z2 * (
            0.05265332 + z2 * -0.01172120)))))
    a = jnp.where(ay > ax, math.pi / 2 - a, a)
    a = jnp.where(x < 0, math.pi - a, a)
    return jnp.where(y < 0, -a, a)


def candidate_bins(m10, m01):
    """The two steering bins round(t -+ BIN_MARGIN) mod 12 around the
    estimated angle t (bin units) — the oracle's bin is one of them
    whenever the estimate is within BIN_MARGIN bins of atan2."""
    t = approx_atan2(m01, m10) * _BINS_PER_RAD
    lo = jnp.floor(t - BIN_MARGIN + 0.5).astype(jnp.int32)
    hi = jnp.floor(t + BIN_MARGIN + 0.5).astype(jnp.int32)
    return lo % _N_BINS, hi % _N_BINS


def _tap_patch(sm):
    """The smoothed patch as the taps' MXU operand: an integer patch
    (0-255) as bf16, exact; a float patch as it is, f32."""
    if single_pass_taps(sm.dtype):
        return sm.astype(jnp.bfloat16)
    return sm


def _steered_bits(lut_ref, sm, b):
    """(31, 31) smoothed patch from ``_tap_patch`` + (1, 1) bin ->
    (1, 256) bf16 tau bits.  Each tap is a one-hot matmul (column
    select) then a one-hot masked row sum.  A bf16 patch holds integers
    up to 255 (exact in bf16) and each tap output has one nonzero
    product, the pixel times 1, so one bf16 pass accumulated in f32
    returns the pixel exactly; an f32 patch takes ``Precision.HIGHEST``,
    exact too.  So p(B) - p(A) is the correctly rounded difference of
    the two pixel values and its sign is exact."""
    binoh = (jax.lax.broadcasted_iota(jnp.int32, (_N_BINS, 1), 0)
             == b).astype(jnp.int32)
    ya, xa, yb, xb = (jnp.sum(lut_ref[q] * binoh, axis=0, keepdims=True)
                      for q in range(4))                   # (1, 256) each
    pos = jax.lax.broadcasted_iota(jnp.int32, (PATCH, _N_PAIRS), 0)
    precision = (None if sm.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)

    def tap(yq, xq):
        cols = jnp.dot(sm, (pos == xq).astype(sm.dtype),
                       preferred_element_type=jnp.float32,
                       precision=precision)                # (31, 256)
        return jnp.sum(jnp.where(pos == yq, cols, 0.0), axis=0,
                       keepdims=True)

    return (tap(yb, xb) - tap(ya, xa) > 0.0).astype(jnp.bfloat16)


def _pack(bits, pack_ref):
    """(1, 256) bf16 bits -> (1, 8) uint32 words (bit i of word i // 32,
    ``ref.pack_bits``'s layout), in one bf16 pass: 0/1 times distinct
    powers of two below 2^16, summed exactly in f32."""
    halves = jnp.dot(bits, pack_ref[...],
                     preferred_element_type=jnp.float32)   # (1, 16)
    halves = halves.astype(jnp.int32).astype(jnp.uint32)
    return halves[:, :8] | (halves[:, 8:] << 16)


def _describe_kernel(xy_ref, hw_ref, lut_ref, pack_ref, raw_ref, sm_ref,
                     mom_ref, bins_ref, desc_ref, *, kb: int, ktot: int):
    bb = pl.program_id(0)
    kk = pl.program_id(1)
    true_h = hw_ref[2 * kk]
    true_w = hw_ref[2 * kk + 1]
    row2 = jax.lax.broadcasted_iota(jnp.int32, (kb, 2), 0)
    col2 = jax.lax.broadcasted_iota(jnp.int32, (kb, 2), 1)
    row16 = jax.lax.broadcasted_iota(jnp.int32, (kb, 16), 0)

    def keypoint(t, carry):
        # A rolled loop over the block's keypoints keeps the kernel body
        # (and its compile) one keypoint long.
        mom, bins, desc = carry
        base = 2 * (bb * ktot + kk * kb + t)
        x = jnp.clip(xy_ref[base], 0, true_w - 1)
        y = jnp.clip(xy_ref[base + 1], 0, true_h - 1)
        raw = aligned_window(raw_ref, y, x, PATCH, PATCH, WIN_H, WIN_W)
        sm = _tap_patch(
            aligned_window(sm_ref, y, x, PATCH, PATCH, WIN_H, WIN_W))
        m10, m01 = _moments(raw)
        b_lo, b_hi = candidate_bins(m10, m01)
        words = jnp.concatenate(
            [_pack(_steered_bits(lut_ref, sm, b), pack_ref)
             for b in (b_lo, b_hi)], axis=1)                # (1, 16)
        mom = jnp.where((row2 == t) & (col2 == 0), m10, mom)
        mom = jnp.where((row2 == t) & (col2 == 1), m01, mom)
        bins = jnp.where((row2 == t) & (col2 == 0), b_lo, bins)
        bins = jnp.where((row2 == t) & (col2 == 1), b_hi, bins)
        desc = jnp.where(row16 == t, words, desc)
        return mom, bins, desc

    mom, bins, desc = jax.lax.fori_loop(
        0, kb, keypoint, (jnp.zeros((kb, 2), jnp.float32),
                          jnp.zeros((kb, 2), jnp.int32),
                          jnp.zeros((kb, 16), jnp.uint32)))
    mom_ref[...] = mom
    bins_ref[...] = bins
    desc_ref[...] = desc


def _cast_slab(x):
    """Keep integer image slabs uint8 (the integer datapath); float
    slabs run f32."""
    return x.astype(jnp.uint8 if jnp.issubdtype(x.dtype, jnp.integer)
                    else jnp.float32)


def _block_level(kk, level_offsets):
    """Pyramid level of K-block ``kk``: keypoint blocks are level-sorted,
    so the level is the number of level start-offsets at or below kk.
    ``level_offsets`` is a STATIC tuple (offsets[l] = first block of
    level l) — the sum unrolls to L-1 compares on the traced block id,
    legal inside a BlockSpec index map."""
    lvl = 0
    for off in level_offsets[1:]:
        lvl = lvl + jnp.where(kk >= off, 1, 0)
    return lvl


@functools.partial(jax.jit, static_argnames=(
    "level_offsets", "kb", "interpret"))
def describe_fused_pyramid_pallas(raw_slabs: jnp.ndarray,
                                  sm_slabs: jnp.ndarray, xy: jnp.ndarray,
                                  hw: jnp.ndarray, *,
                                  level_offsets: tuple[int, ...],
                                  kb: int = KP_BLOCK,
                                  interpret: bool = False):
    """Whole-frame sparse launch: ALL cameras x ALL levels in ONE
    ``pallas_call`` whose grid walks (camera, level-sorted K-block).

    raw_slabs/sm_slabs: (L*B, Hc, Wc) float32 or uint8 — level-major
    flattened level slab pairs, each edge-padded by RADIUS and out to a
    COMMON canvas with room for the aligned (WIN_H, WIN_W) patch window
    of any clamped keypoint (``ops.py`` owns that padding).  xy:
    (B, Ktot, 2) int32, keypoints level-sorted with each level's block
    padded to a kb multiple.  hw: (Ktot/kb, 2) int32 per-K-block true
    (h, w) for the coordinate clamp.  level_offsets: static per-level
    first-block offsets — each grid step resolves its slab pair through
    ``_block_level`` in the index maps.

    Returns (moments (B, Ktot, 2) f32, bins (B, Ktot, 2) int32 — the
    two candidate steering bins, desc (B, Ktot, 16) uint32 — the
    descriptor steered by each candidate, words 0-7 and 8-15)."""
    b, k = xy.shape[0], xy.shape[1]
    _, hc, wc = raw_slabs.shape
    grid = (b, k // kb)
    kern = functools.partial(_describe_kernel, kb=int(kb), ktot=int(k))

    def slab_index(bb, kk, *_):
        return (_block_level(kk, level_offsets) * b + bb, 0, 0)

    def const(*_):
        return (0, 0)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((4, _N_BINS, _N_PAIRS), lambda *_: (0, 0, 0)),
                pl.BlockSpec((_N_PAIRS, 16), const),
                pl.BlockSpec((None, hc, wc), slab_index,
                             pipeline_mode=pl.Buffered(1)),
                pl.BlockSpec((None, hc, wc), slab_index,
                             pipeline_mode=pl.Buffered(1)),
            ],
            out_specs=[
                pl.BlockSpec((None, kb, 2), lambda bb, kk, *_: (bb, kk, 0)),
                pl.BlockSpec((None, kb, 2), lambda bb, kk, *_: (bb, kk, 0)),
                pl.BlockSpec((None, kb, 16),
                             lambda bb, kk, *_: (bb, kk, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, k, 2), jnp.float32),
            jax.ShapeDtypeStruct((b, k, 2), jnp.int32),
            jax.ShapeDtypeStruct((b, k, 16), jnp.uint32),
        ],
        interpret=interpret,
        name="describe_fused_pyramid_pallas",
    )(xy.astype(jnp.int32).reshape(-1), hw.astype(jnp.int32).reshape(-1),
      jnp.asarray(steer_planes()), jnp.asarray(pack_weights()),
      _cast_slab(raw_slabs), _cast_slab(sm_slabs))
