"""Pallas TPU kernels: single-launch Feature Matcher megakernel.

The paper's Feature Matcher is ONE hardware block (Sec. III-D): Search
Region Decision, Hamming Compare and SAD Correction / Disparity
Computing stream through a shared datapath.  Before this kernel our FM
stage was three pieces per stereo pair — the ``hamming_match`` kernel, a
host-graph gather chain (full-image pad + 2*K vmapped ``dynamic_slice``
per pair, twice) and the ``sad_search`` kernel.  Here the WHOLE stage is
one ``pallas_call`` batched over stereo pairs:

  * Grid = (pair, K-block, M-block); the M axis is the inner sequential
    dimension and accumulates the masked Hamming running-argmin into
    revisited output blocks exactly as ``hamming_match._kernel`` does
    (ties resolve to the LOWEST right index — first-occurrence argmin).
    Alongside (dist, idx) the sweep accumulates the winning right
    feature's float (x, y), extracted per tile by an exact one-hot
    masked sum — so no cross-block gather is ever needed.
  * Once the sweep completes (last M step), the SAME kernel step
    resolves the effective right feature (index 0 when the match fails
    the ``max_hamming``/validity gates, mirroring
    ``MatchSet.right_index``'s ``where(valid, idx, 0)``), reads the
    P x P left patch and the (P, P + 2R) right strip directly from the
    level-0 image slabs resident in VMEM (dynamic in-kernel slicing a la
    ``describe_fused`` — gather-free), runs the SAD sweep in int32 and
    emits the argmin.  Per traced frame the FM stage is ONE launch.

``match_fused_pallas`` is the match-only variant (no images, no SAD) —
the same pair-folded grid serving ``stereo_match`` / ``temporal_match``
in one launch; ``sad_fused_pallas`` is the SAD-only variant serving
``sad_rectify`` with caller-provided match indices, replacing its
host-graph patch-gather chain with the same in-kernel reads.

Boundary semantics are pinned to the gather oracle
(``ref.gather_patches`` / ``ref.gather_patches_bruteforce``): patch
centers are rounded (round-half-even) and clamped into the true image,
and the slabs are edge-padded by the patch radii, so window pixels
replicate the border exactly like the oracle's ``jnp.pad(mode="edge")``.
All SAD arithmetic is int32 (associative), so any summation order is
bit-exact against the oracle.

Mosaic layout: the right side enters transposed — (8, M) descriptors
and (4, M) meta — so the Hamming sweep's right rows are lane-dense;
per-feature outputs are (P, K, 1) columns and the fallback coordinates
a (P, 1, 2) table, blocks that span their minor dims; patch starts are
scalar reads of the meta rows; each patch or strip is read as the
(8, 128)-aligned window that contains it and rotated into place
(``describe_fused.aligned_window``); the level-0 slabs are single
buffered, since their block index changes only with the pair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.describe_fused import _cast_slab, aligned_window
from repro.kernels.hamming_match import BIG, first_argmin, masked_hamming

FM_BK = 8         # left-feature tile of the fused/SAD kernels
FM_BM = 128       # right-feature tile (inner sequential sweep)
MO_BK = 128       # left-feature tile of the match-only kernel
SAD_WIN_H = 24    # aligned window rows: patch (<= 17) + 7 sublane shift
SAD_WIN_W = 256   # aligned window lanes: strip (<= 129) + 127 lane shift


def _clamped_start(coord, limit: int):
    """Float center coordinate -> int32 patch start in the edge-padded
    slab: round-half-even then clamp into the true image, exactly
    ``ref.gather_patches``'s center clamp."""
    return jnp.clip(jnp.round(coord).astype(jnp.int32), 0, limit - 1)


def _sad_sums(il_ref, ir_ref, xl, yl, xr, yr, *, patch: int, sweep: int):
    """One feature's SAD table: the (patch, patch) left window against
    each offset of the (patch, patch + sweep - 1) right strip, read from
    the VMEM slabs at the given clamped starts.  int32 throughout —
    bit-exact against ``ref.sad_search`` for any summation order.
    Returns ``sweep`` (1, 1) sums."""
    lp = aligned_window(il_ref, yl, xl, patch, patch, SAD_WIN_H,
                        SAD_WIN_W).astype(jnp.int32)
    rs = aligned_window(ir_ref, yr, xr, patch, patch + sweep - 1,
                        SAD_WIN_H, SAD_WIN_W).astype(jnp.int32)
    return [jnp.sum(jnp.abs(lp - rs[:, s:s + patch]), axis=(0, 1),
                    keepdims=True) for s in range(sweep)]


def _sad_argmin(sums):
    """First-occurrence argmin over a list of (1, 1) sums, as (1, 1)."""
    best, arg = sums[0], jnp.zeros_like(sums[0])
    for s, v in enumerate(sums[1:], start=1):
        better = v < best
        best = jnp.where(better, v, best)
        arg = jnp.where(better, s, arg)
    return arg


def _sweep_step(dist_ref, idx_ref, dist, j):
    """Fold one (K-block, M-block) distance tile into the running
    first-occurrence argmin; returns (improved, in-tile argmin), each
    (bk, 1)."""
    tile_best = jnp.min(dist, axis=1, keepdims=True)
    am = first_argmin(dist, tile_best)
    improved = tile_best < dist_ref[...]
    idx_ref[...] = jnp.where(improved, am + j * dist.shape[1],
                             idx_ref[...])
    dist_ref[...] = jnp.where(improved, tile_best, dist_ref[...])
    return improved, am


def _match_rectify_kernel(dl_ref, ml_ref, dr_ref, mr_ref, xy0_ref,
                          il_ref, ir_ref,
                          dist_ref, idx_ref, rxy_ref, sad_ref, *,
                          row_band: float, max_disparity: float,
                          max_hamming: int, patch: int, sweep: int,
                          n_m: int, true_h: int, true_w: int, bk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dist_ref[...] = jnp.full_like(dist_ref, BIG)
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        rxy_ref[...] = jnp.zeros_like(rxy_ref)
        sad_ref[...] = jnp.zeros_like(sad_ref)

    ml = ml_ref[...]                       # (bk, 4) f32: x, y, level, valid
    mr = mr_ref[...]                       # (4, BM) f32, transposed
    dist = masked_hamming(dl_ref[...], ml, dr_ref[...], mr,
                          row_band=row_band, max_disparity=max_disparity)

    # Compare: running argmin, plus the winner's float (x, y) extracted
    # by an exact one-hot masked sum (one nonzero term -> a bit-exact
    # f32 copy of the winning meta row, no cross-block gather).
    improved, am = _sweep_step(dist_ref, idx_ref, dist, j)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1) == am)
    xw = jnp.sum(jnp.where(onehot, mr[0:1, :], 0.0), axis=1, keepdims=True)
    yw = jnp.sum(jnp.where(onehot, mr[1:2, :], 0.0), axis=1, keepdims=True)
    rxy_ref[:, 0:1] = jnp.where(improved, xw, rxy_ref[:, 0:1])
    rxy_ref[:, 1:2] = jnp.where(improved, yw, rxy_ref[:, 1:2])

    @pl.when(j == n_m - 1)
    def _sad():
        # Resolve the effective right feature: the accumulated winner
        # when the match passes the acceptance gates, else right
        # feature 0 — mirroring MatchSet.right_index's where(valid,
        # idx, 0) so downstream reads are bit-identical to the oracle.
        ok = ((idx_ref[...] >= 0) & (dist_ref[...] <= max_hamming)
              & (ml[:, 3:4] > 0.5))
        rxy_ref[:, 0:1] = jnp.where(ok, rxy_ref[:, 0:1], xy0_ref[0, 0])
        rxy_ref[:, 1:2] = jnp.where(ok, rxy_ref[:, 1:2], xy0_ref[0, 1])
        rows = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)

        def feature(kk, sad):
            xl = _clamped_start(ml_ref[kk, 0], true_w)
            yl = _clamped_start(ml_ref[kk, 1], true_h)
            xr = _clamped_start(rxy_ref[kk, 0], true_w)
            yr = _clamped_start(rxy_ref[kk, 1], true_h)
            arg = _sad_argmin(_sad_sums(il_ref, ir_ref, xl, yl, xr, yr,
                                        patch=patch, sweep=sweep))
            return jnp.where(rows == kk, arg, sad)

        sad_ref[...] = jax.lax.fori_loop(0, bk, feature,
                                         jnp.zeros((bk, 1), jnp.int32))


def _match_only_kernel(dl_ref, ml_ref, dr_ref, mr_ref,
                       dist_ref, idx_ref, *,
                       row_band: float, max_disparity: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dist_ref[...] = jnp.full_like(dist_ref, BIG)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    dist = masked_hamming(dl_ref[...], ml_ref[...], dr_ref[...],
                          mr_ref[...], row_band=row_band,
                          max_disparity=max_disparity)
    _sweep_step(dist_ref, idx_ref, dist, j)


def _sad_only_kernel(xyl_ref, xyr_ref, il_ref, ir_ref, tab_ref, *,
                     patch: int, sweep: int, true_h: int, true_w: int,
                     bk: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bk, sweep), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bk, sweep), 1)

    def feature(kk, tab):
        xl = _clamped_start(xyl_ref[kk, 0], true_w)
        yl = _clamped_start(xyl_ref[kk, 1], true_h)
        xr = _clamped_start(xyr_ref[kk, 0], true_w)
        yr = _clamped_start(xyr_ref[kk, 1], true_h)
        sums = _sad_sums(il_ref, ir_ref, xl, yl, xr, yr, patch=patch,
                         sweep=sweep)
        for s, v in enumerate(sums):
            tab = jnp.where((rows == kk) & (cols == s), v, tab)
        return tab

    tab_ref[...] = jax.lax.fori_loop(0, bk, feature,
                                     jnp.zeros((bk, sweep), jnp.int32))


def _column(n_pairs: int, k: int, dtype):
    return jax.ShapeDtypeStruct((n_pairs, k, 1), dtype)


def _resident(shape, index_map):
    """Block held for a whole pair: single-buffered, since its index
    changes only with the outermost grid axis."""
    return pl.BlockSpec(shape, index_map, pipeline_mode=pl.Buffered(1))


@functools.partial(jax.jit, static_argnames=(
    "row_band", "max_disparity", "max_hamming", "patch", "sad_range",
    "true_h", "true_w", "interpret"))
def match_rectify_fused_pallas(desc_l, meta_l, desc_r_t, meta_r_t, xy0,
                               img_l_padded, img_r_padded, *,
                               row_band: float, max_disparity: float,
                               max_hamming: int, patch: int,
                               sad_range: int, true_h: int, true_w: int,
                               interpret: bool = False):
    """The FM megakernel: ONE launch for Hamming match + SAD sweep of a
    whole frame, batched over stereo pairs.

    desc_l: (P, K, 8) uint32, meta_l: (P, K, 4) float32 rows of
    (x, y, level, valid); desc_r_t: (P, 8, M) uint32 and meta_r_t:
    (P, 4, M) float32, the right side transposed (K % FM_BK ==
    M % FM_BM == 0 — ``ops.py`` pads); xy0: (P, 1, 2) float32 — right
    feature 0's (x, y) per pair, the oracle's fallback read when a match
    fails the gates; img_*_padded: (P, Hp, Wp) level-0 slabs edge-padded
    by the patch radii (left: P//2 each side; right: P//2 + sad_range
    in x) and out to room for the aligned SAD windows (alignment region
    never read).  Returns (dist (P, K, 1) int32 [BIG when no candidate],
    idx (P, K, 1) int32 [-1], rxy (P, K, 2) float32 — the effective
    right feature's float coords, sad (P, K, 1) int32 — SAD-sweep argmin
    in [0, 2*sad_range]).
    """
    n_pairs, k = desc_l.shape[0], desc_l.shape[1]
    m = desc_r_t.shape[2]
    _, hlp, wlp = img_l_padded.shape
    _, hrp, wrp = img_r_padded.shape
    sweep = 2 * sad_range + 1
    grid = (n_pairs, k // FM_BK, m // FM_BM)
    kern = functools.partial(
        _match_rectify_kernel, row_band=float(row_band),
        max_disparity=float(max_disparity), max_hamming=int(max_hamming),
        patch=int(patch), sweep=int(sweep), n_m=m // FM_BM,
        true_h=int(true_h), true_w=int(true_w), bk=FM_BK)
    col = pl.BlockSpec((None, FM_BK, 1), lambda p, i, j: (p, i, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, FM_BK, 8), lambda p, i, j: (p, i, 0)),
            pl.BlockSpec((None, FM_BK, 4), lambda p, i, j: (p, i, 0)),
            pl.BlockSpec((None, 8, FM_BM), lambda p, i, j: (p, 0, j)),
            pl.BlockSpec((None, 4, FM_BM), lambda p, i, j: (p, 0, j)),
            pl.BlockSpec((None, 1, 2), lambda p, i, j: (p, 0, 0)),
            _resident((None, hlp, wlp), lambda p, i, j: (p, 0, 0)),
            _resident((None, hrp, wrp), lambda p, i, j: (p, 0, 0)),
        ],
        out_specs=[
            col, col,
            pl.BlockSpec((None, FM_BK, 2), lambda p, i, j: (p, i, 0)),
            col,
        ],
        out_shape=[
            _column(n_pairs, k, jnp.int32),
            _column(n_pairs, k, jnp.int32),
            jax.ShapeDtypeStruct((n_pairs, k, 2), jnp.float32),
            _column(n_pairs, k, jnp.int32),
        ],
        interpret=interpret,
        name="match_rectify_fused_pallas",
    )(desc_l, meta_l, desc_r_t, meta_r_t, xy0.astype(jnp.float32),
      _cast_slab(img_l_padded), _cast_slab(img_r_padded))


@functools.partial(jax.jit, static_argnames=(
    "row_band", "max_disparity", "interpret"))
def match_fused_pallas(desc_l, meta_l, desc_r_t, meta_r_t, *,
                       row_band: float, max_disparity: float,
                       interpret: bool = False):
    """Match-only variant: the same pair-folded (pair, K-block, M-block)
    grid without images or SAD — ``stereo_match`` / ``temporal_match``
    in ONE launch for all pairs.  desc_l/meta_l: (P, K, 8)/(P, K, 4);
    desc_r_t/meta_r_t: (P, 8, M)/(P, 4, M), transposed (K % MO_BK ==
    M % FM_BM == 0); returns (dist (P, K, 1) int32, idx (P, K, 1) int32
    [-1 when no candidate])."""
    n_pairs, k = desc_l.shape[0], desc_l.shape[1]
    m = desc_r_t.shape[2]
    grid = (n_pairs, k // MO_BK, m // FM_BM)
    kern = functools.partial(_match_only_kernel, row_band=float(row_band),
                             max_disparity=float(max_disparity))
    col = pl.BlockSpec((None, MO_BK, 1), lambda p, i, j: (p, i, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, MO_BK, 8), lambda p, i, j: (p, i, 0)),
            pl.BlockSpec((None, MO_BK, 4), lambda p, i, j: (p, i, 0)),
            pl.BlockSpec((None, 8, FM_BM), lambda p, i, j: (p, 0, j)),
            pl.BlockSpec((None, 4, FM_BM), lambda p, i, j: (p, 0, j)),
        ],
        out_specs=[col, col],
        out_shape=[_column(n_pairs, k, jnp.int32),
                   _column(n_pairs, k, jnp.int32)],
        interpret=interpret,
        name="match_fused_pallas",
    )(desc_l, meta_l, desc_r_t, meta_r_t)


@functools.partial(jax.jit, static_argnames=(
    "patch", "sad_range", "true_h", "true_w", "interpret"))
def sad_fused_pallas(xy_l, xy_r, img_l_padded, img_r_padded, *,
                     patch: int, sad_range: int, true_h: int,
                     true_w: int, interpret: bool = False):
    """SAD-only variant for caller-provided match targets
    (``sad_rectify``'s path): in-kernel patch reads replace the
    host-graph pad + 2*K ``dynamic_slice`` gather chain.  xy_*:
    (P, K, 2) float32 centers (K % FM_BK == 0); returns the full
    (P, K, 2*sad_range + 1) int32 SAD table (argmin taken by the
    caller, exactly like ``ops.sad_search``)."""
    n_pairs, k = xy_l.shape[0], xy_l.shape[1]
    _, hlp, wlp = img_l_padded.shape
    _, hrp, wrp = img_r_padded.shape
    sweep = 2 * sad_range + 1
    grid = (n_pairs, k // FM_BK)
    kern = functools.partial(_sad_only_kernel, patch=int(patch),
                             sweep=int(sweep), true_h=int(true_h),
                             true_w=int(true_w), bk=FM_BK)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, FM_BK, 2), lambda p, i: (p, i, 0)),
            pl.BlockSpec((None, FM_BK, 2), lambda p, i: (p, i, 0)),
            _resident((None, hlp, wlp), lambda p, i: (p, 0, 0)),
            _resident((None, hrp, wrp), lambda p, i: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, FM_BK, sweep), lambda p, i: (p, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pairs, k, sweep), jnp.int32),
        interpret=interpret,
        name="sad_fused_pallas",
    )(xy_l.astype(jnp.float32), xy_r.astype(jnp.float32),
      _cast_slab(img_l_padded), _cast_slab(img_r_padded))
