"""Pallas TPU megakernel: fused batched quad-camera ORB frontend.

One VMEM pass per tile emits BOTH per-pixel products the ORB frontend
needs from a level image:

  * the 7x7-Gaussian-smoothed image (input to rBRIEF), and
  * the 3x3-NMS'd FAST-9/16 corner score map (input to top-K).

This is the TPU analog of the paper's frame-multiplexed FE (Sec.
III-B/III-C): the FPGA streams each frame once through a shared FAST +
smoothing datapath, multiplexing all four cameras through one module.
Here the leading grid dimension is a flattened batch of camera images,
so the VPU is time-multiplexed across cameras exactly as the FPGA FE is
time-multiplexed across channels — and each pixel is read from VMEM
once instead of once per op.  Two entry points share one tile body:

  * ``frontend_fused_pallas`` — one launch per pyramid level, batch =
    cameras, true (h, w) static (the PR-1 schedule, kept as the
    per-level oracle path), and
  * ``frontend_fused_pyramid_pallas`` — ONE launch per whole frame,
    batch = cameras x levels with ragged level slabs padded to a common
    tile grid and masked by a per-slab (true_h, true_w) shape table
    (the paper's whole-frame streaming FE, Sec. III-B).

Halo arithmetic: blur and FAST both need a 3-pixel stencil halo; fusing
the 3x3 NMS needs the *raw score* one pixel beyond the tile, and that
score row/column needs its own 3-pixel image halo — hence FUSED_HALO=4
(vs. HALO=3 for the unfused kernels).  Mosaic only takes blocks whose
last two dims are (8, 128)-aligned or span the array, so a (TILE+8)^2
halo window cannot be a block: the input block is instead the full-width
row band (1, TILE+8, W+8) at element row offset i*TILE (``pl.Element``),
fetched once per (slab, tile row) because its index is constant along
the tile-column grid axis, and each step slices its (TILE+8, TILE+8)
window at the 128-aligned lane offset j*TILE.  Two (1, TILE, TILE)
outputs.  MXU-free, pure VPU stencil.

Boundary semantics match the ``ref.py`` oracle chain exactly:
  * image taps outside the true image replicate the edge pixel
    (``ops.py`` edge-pads before tiling), and
  * NMS neighbours outside the true (H, W) image are -1.0 (the constant
    pad of ``ref.nms3``) — the kernel masks by global pixel coordinate,
    which also keeps tile-alignment padding from suppressing real
    corners.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (ARC_LEN, CIRCLE16, GAUSS7_NORM,
                               GAUSS7_WEIGHTS_INT, int_threshold)

TILE_H = 128
TILE_W = 128
FUSED_HALO = 4          # 3 (7x7 blur / FAST circle) + 1 (in-kernel 3x3 NMS)


def arc_extrema(taps):
    """Per-start (min, max) over the 9 contiguous circular taps of each
    FAST-9/16 arc, via block prefix/suffix extrema (van Herk/Gil-Werman
    sliding-window trick on the circular 16-sequence).

    ~half the min/max ops of naively unrolling 16 windows x 8
    comparisons, and BIT-exact — min/max are associative and
    commutative, so reassociation cannot change any result.  Shared by
    the Pallas kernel body and the interpret-free jnp fallback; shape-
    agnostic (works on any list of same-shape arrays).

    taps: list of 16 arrays.  Returns (arc_min, arc_max): lists of 16
    arrays where arc_min[s] = min(taps[s..s+8 mod 16]) etc.
    """
    wlen = ARC_LEN
    ext = list(taps) + list(taps[:wlen - 1])       # unroll the wrap
    m = len(ext)
    pmin = [None] * m
    pmax = [None] * m
    for i in range(m):
        if i % wlen == 0:
            pmin[i], pmax[i] = ext[i], ext[i]
        else:
            pmin[i] = jnp.minimum(pmin[i - 1], ext[i])
            pmax[i] = jnp.maximum(pmax[i - 1], ext[i])
    smin = [None] * m
    smax = [None] * m
    for i in reversed(range(m)):
        if i % wlen == wlen - 1 or i == m - 1:
            smin[i], smax[i] = ext[i], ext[i]
        else:
            smin[i] = jnp.minimum(smin[i + 1], ext[i])
            smax[i] = jnp.maximum(smax[i + 1], ext[i])
    arc_min, arc_max = [], []
    for i in range(len(taps)):
        j = i + wlen - 1
        if i % wlen == 0:                           # window == one block
            arc_min.append(pmin[j])
            arc_max.append(pmax[j])
        else:
            arc_min.append(jnp.minimum(smin[i], pmin[j]))
            arc_max.append(jnp.maximum(smax[i], pmax[j]))
    return arc_min, arc_max


def fast_score_from_taps(taps, threshold: float):
    """FAST-9/16 score from the 16 circle-tap difference arrays:
    max over arc starts of (min over bright arc, -max over dark arc),
    thresholded to 0.  Exact; shared by kernel and jnp fallback."""
    arc_min, arc_max = arc_extrema(taps)
    bright = arc_min[0]
    dark = arc_max[0]
    for s in range(1, len(taps)):
        bright = jnp.maximum(bright, arc_min[s])
        dark = jnp.minimum(dark, arc_max[s])
    score = jnp.maximum(bright, -dark)
    # Integer taps (the uint8 datapath) compare against floor(threshold)
    # — exactly ``score > threshold`` for integer scores (ref.int_threshold).
    if jnp.issubdtype(score.dtype, jnp.integer):
        thr = jnp.asarray(int_threshold(threshold), score.dtype)
    else:
        thr = jnp.asarray(threshold, score.dtype)
    return jnp.where(score > thr, score, jnp.zeros_like(score))


def _tile_outputs(x, true_h, true_w, *, threshold: float, nms: bool,
                  quantized: bool, tile_h: int, tile_w: int):
    """Shared per-tile body: (tile_h + 8, tile_w + 8) input window ->
    (blur, score), each (tile_h, tile_w).  ``true_h``/``true_w`` may be
    static Python ints (per-level launch) or traced scalars read from the
    whole-pyramid shape table — the NMS boundary mask broadcasts either
    way, so both launch schedules run the exact same math.

    Dtype is static at trace time, so the integer datapath (paper Sec.
    III word length: uint8 slab in, int32 accumulators, uint8 blur +
    int16 score out) and the f32 datapath share this one body — the
    branch below selects accumulator/literal dtypes, nothing else."""
    fh = FUSED_HALO
    integer = jnp.issubdtype(x.dtype, jnp.integer)
    if integer:
        x = x.astype(jnp.int32)        # int32 accumulate, uint8 values

    # ---- 7x7 separable Gaussian (needs halo 3: rows/cols 1..tile+7) ----
    w = ([int(v) for v in GAUSS7_WEIGHTS_INT] if integer
         else [float(v) for v in GAUSS7_WEIGHTS_INT])
    horiz = None
    for k in range(7):
        term = w[k] * x[1:tile_h + 7, 1 + k:1 + k + tile_w]
        horiz = term if horiz is None else horiz + term    # (tile_h+6, tile_w)
    vert = None
    for k in range(7):
        term = w[k] * horiz[k:k + tile_h, :]
        vert = term if vert is None else vert + term       # (tile_h, tile_w)
    norm2 = GAUSS7_NORM * GAUSS7_NORM
    if integer:
        # Exact round-half-up division; vert + 648 < 2^24, the same
        # quotient the f32 floor computes (ref.gaussian_blur7_u8).
        blur = ((vert + norm2 // 2) // norm2).astype(jnp.uint8)
    elif quantized:
        blur = jnp.floor((vert + norm2 / 2.0) / float(norm2))
    else:
        blur = vert / float(norm2)

    # ---- FAST-9/16 raw score on the (tile+2)^2 window (1-px NMS rim) ----
    eh, ew = tile_h + 2, tile_w + 2
    center = x[fh - 1:fh - 1 + eh, fh - 1:fh - 1 + ew]
    taps = [
        x[fh - 1 + dy:fh - 1 + dy + eh, fh - 1 + dx:fh - 1 + dx + ew] - center
        for dx, dy in CIRCLE16
    ]
    score = fast_score_from_taps(taps, threshold)

    # Mask pixels outside the true image to -1.0 — the ref.nms3 constant
    # pad — so image borders and tile-alignment padding never win NMS.
    i = pl.program_id(1)
    j = pl.program_id(2)
    rows = i * tile_h - 1 + jax.lax.broadcasted_iota(jnp.int32, (eh, ew), 0)
    cols = j * tile_w - 1 + jax.lax.broadcasted_iota(jnp.int32, (eh, ew), 1)
    inside = ((rows >= 0) & (rows < true_h) & (cols >= 0) & (cols < true_w))
    score = jnp.where(inside, score, jnp.asarray(-1, score.dtype))

    cs = score[1:1 + tile_h, 1:1 + tile_w]
    if nms:
        # Separable 3x3 max INCLUDING the center: cs >= max(cs, nbrs)
        # iff cs >= max(nbrs), so the NMS decision is unchanged while
        # the 8-neighbour max folds into 2 + 2 row/column maxes.
        rmax = jnp.maximum(jnp.maximum(score[:eh - 2, :], score[1:eh - 1, :]),
                           score[2:, :])
        nmax = jnp.maximum(jnp.maximum(rmax[:, :ew - 2], rmax[:, 1:ew - 1]),
                           rmax[:, 2:])
        out = (jnp.where(cs >= nmax, cs, jnp.zeros_like(cs))
               * (cs > 0).astype(cs.dtype))
    else:
        out = jnp.maximum(cs, jnp.zeros_like(cs))  # strip the -1 sentinel
    if integer:
        out = out.astype(jnp.int16)        # FAST scores live in [0, 255]
    return blur, out


def _slab_dtypes(padded, quantized: bool):
    """Resolve the (input slab, (blur, score) output dtypes) pair from
    the slab dtype: integer slabs run the uint8 datapath (requires the
    quantized blur — the float blur is not representable in uint8),
    float slabs the f32 one."""
    if jnp.issubdtype(padded.dtype, jnp.integer):
        if not quantized:
            raise ValueError(
                "uint8 datapath requires quantized=True (the float "
                "Gaussian is not representable in a uint8 slab)")
        return padded.astype(jnp.uint8), (jnp.uint8, jnp.int16)
    return padded.astype(jnp.float32), (jnp.float32, jnp.float32)


def halo_window(x_ref, halo: int, tile_w: int):
    """This grid step's (tile_h + 2*halo, tile_w + 2*halo) window of a
    full-width row-band block: the lane offset j * tile_w is a multiple
    of 128, the alignment Mosaic needs for a dynamic lane slice."""
    j = pl.program_id(x_ref.ndim - 1)
    col = pl.multiple_of(j * tile_w, tile_w)
    if x_ref.ndim == 3:
        return x_ref[0, :, pl.ds(col, tile_w + 2 * halo)]
    return x_ref[:, pl.ds(col, tile_w + 2 * halo)]


def row_band_spec(rows: int, width: int, batched: bool, tile_h: int):
    """BlockSpec of a full-width row band starting at element row
    i * tile_h (halo rows included); index constant along the
    tile-column axis, so the band is fetched once per tile row.  The
    index map takes any trailing scalar-prefetch refs."""
    if batched:
        return pl.BlockSpec(
            (pl.Element(1), pl.Element(rows), pl.Element(width)),
            lambda bb, i, j, *_: (bb, i * tile_h, 0))
    return pl.BlockSpec((pl.Element(rows), pl.Element(width)),
                        lambda i, j, *_: (i * tile_h, 0))


def _kernel(x_ref, blur_ref, score_ref, *, threshold: float, nms: bool,
            quantized: bool, true_h: int, true_w: int,
            tile_h: int, tile_w: int):
    blur, out = _tile_outputs(halo_window(x_ref, FUSED_HALO, tile_w),
                              true_h, true_w, threshold=threshold,
                              nms=nms, quantized=quantized,
                              tile_h=tile_h, tile_w=tile_w)
    blur_ref[...] = blur[None]
    score_ref[...] = out[None]


def _kernel_pyramid(hw_ref, x_ref, blur_ref, score_ref, *, threshold: float,
                    nms: bool, quantized: bool, tile_h: int, tile_w: int):
    """Whole-pyramid variant: the slab's true (h, w) comes from the
    scalar-prefetched (SMEM) shape table instead of static kwargs —
    every other instruction is shared with the per-level kernel."""
    bb = pl.program_id(0)
    blur, out = _tile_outputs(halo_window(x_ref, FUSED_HALO, tile_w),
                              hw_ref[2 * bb], hw_ref[2 * bb + 1],
                              threshold=threshold, nms=nms,
                              quantized=quantized,
                              tile_h=tile_h, tile_w=tile_w)
    blur_ref[...] = blur[None]
    score_ref[...] = out[None]


@functools.partial(jax.jit, static_argnames=(
    "threshold", "nms", "quantized", "true_h", "true_w", "interpret"))
def frontend_fused_pallas(padded: jnp.ndarray, *, threshold: float,
                          nms: bool = True, quantized: bool = True,
                          true_h: int, true_w: int,
                          interpret: bool = False):
    """padded: (B, H + 8, W + 8) float32 OR uint8, edge-padded by
    FUSED_HALO and tile-aligned (H % TILE_H == 0, W % TILE_W == 0 —
    ``ops.py`` guarantees this).  (true_h, true_w) is the un-tile-padded
    image size used for the NMS boundary mask.  Returns (blur, score):
    (B, H, W) float32 pair for float input, (uint8 blur, int16 score)
    for uint8 input (the integer datapath — 4x less VMEM per resident
    tile, same values on quantized images)."""
    padded, out_dtypes = _slab_dtypes(padded, quantized)
    b = padded.shape[0]
    h = padded.shape[1] - 2 * FUSED_HALO
    w = padded.shape[2] - 2 * FUSED_HALO
    grid = (b, h // TILE_H, w // TILE_W)
    kern = functools.partial(
        _kernel, threshold=float(threshold), nms=bool(nms),
        quantized=bool(quantized), true_h=int(true_h), true_w=int(true_w),
        tile_h=TILE_H, tile_w=TILE_W)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[row_band_spec(TILE_H + 2 * FUSED_HALO,
                                w + 2 * FUSED_HALO, True, TILE_H)],
        out_specs=[
            pl.BlockSpec((1, TILE_H, TILE_W), lambda bb, i, j: (bb, i, j)),
            pl.BlockSpec((1, TILE_H, TILE_W), lambda bb, i, j: (bb, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, w), out_dtypes[0]),
            jax.ShapeDtypeStruct((b, h, w), out_dtypes[1]),
        ],
        interpret=interpret,
        name="frontend_fused_pallas",
    )(padded)


@functools.partial(jax.jit, static_argnames=(
    "threshold", "nms", "quantized", "interpret"))
def frontend_fused_pyramid_pallas(padded: jnp.ndarray, hw: jnp.ndarray, *,
                                  threshold: float, nms: bool = True,
                                  quantized: bool = True,
                                  interpret: bool = False):
    """Whole-pyramid dense launch: ALL cameras x ALL levels in ONE
    ``pallas_call`` whose grid walks (slab, tile_i, tile_j).

    padded: (N, Hc + 8, Wc + 8) float32 — N = levels x cameras flattened
    level-major; every ragged level slab is edge-padded by FUSED_HALO and
    out to the COMMON tile-aligned (Hc, Wc) canvas (``ops.py`` owns that
    padding).  hw: (N, 2) int32 per-slab (true_h, true_w) — the shape
    table the kernel masks by, so tiles that fall in a small level's
    padding region emit only the -1/0 sentinels and never win NMS.
    Returns (blur, score), each (N, Hc, Wc): float32 pair for float
    input, (uint8, int16) for uint8 slabs (integer datapath); callers
    slice each slab back to its true shape.  The shape table is
    flattened to (2N,) and scalar-prefetched into SMEM.
    """
    padded, out_dtypes = _slab_dtypes(padded, quantized)
    n = padded.shape[0]
    h = padded.shape[1] - 2 * FUSED_HALO
    w = padded.shape[2] - 2 * FUSED_HALO
    grid = (n, h // TILE_H, w // TILE_W)
    kern = functools.partial(
        _kernel_pyramid, threshold=float(threshold), nms=bool(nms),
        quantized=bool(quantized), tile_h=TILE_H, tile_w=TILE_W)
    out_block = pl.BlockSpec((1, TILE_H, TILE_W),
                             lambda bb, i, j, hw_ref: (bb, i, j))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[row_band_spec(TILE_H + 2 * FUSED_HALO,
                                    w + 2 * FUSED_HALO, True, TILE_H)],
            out_specs=[out_block, out_block],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, h, w), out_dtypes[0]),
            jax.ShapeDtypeStruct((n, h, w), out_dtypes[1]),
        ],
        interpret=interpret,
        name="frontend_fused_pyramid_pallas",
    )(hw.astype(jnp.int32).reshape(-1), padded)
