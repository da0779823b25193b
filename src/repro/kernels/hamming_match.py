"""Pallas TPU kernel: fused stereo feature matcher.

Implements the paper's Feature Matcher front half (Sec. III-D) as ONE
kernel: Search Region Decision (epipolar row band + disparity range +
same pyramid level + validity) fused with Distance Computing and Compare
(256-bit Hamming via SWAR popcount, running argmin) — exactly the fusion
the FPGA performs in hardware, which avoids materializing the K x M
distance matrix in HBM.

Grid: (K / BK, M / BM); the M axis is the inner sequential dimension and
accumulates a running (best_dist, best_idx) into the output block
(revisited across the inner grid steps — the Pallas accumulation
pattern).  Ties resolve to the lowest right-feature index, matching the
jnp oracle's first-occurrence argmin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BK = 128          # left-feature tile
BM = 128          # right-feature tile
BIG = 1 << 20     # sentinel distance for masked-out pairs


def _popcount32(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def masked_hamming(dl, ml, dr_t, mr_t, *, row_band: float,
                   max_disparity: float):
    """(BK, 8) uint32 left descriptors + (BK, 4) (x, y, level, valid)
    meta against the TRANSPOSED right side — (8, BM) descriptors and
    (4, BM) meta, so every right-side row is lane-dense — -> (BK, BM)
    int32 Hamming distances with the Search Region Decision (paper Sec.
    III-D) fused as a BIG-sentinel mask.  The shared front half of every
    matcher kernel body — this per-pair kernel and the pair-folded
    grids of ``matcher_fused.py``."""
    # Hamming distance, accumulated word-by-word to keep VMEM small.
    dist = jnp.zeros((dl.shape[0], dr_t.shape[1]), jnp.int32)
    for word in range(dl.shape[1]):
        x = jnp.bitwise_xor(dl[:, word:word + 1], dr_t[word:word + 1, :])
        dist = dist + _popcount32(x)

    dx = ml[:, 0:1] - mr_t[0:1, :]                         # x_L - x_R
    dy = jnp.abs(ml[:, 1:2] - mr_t[1:2, :])
    same_level = ml[:, 2:3] == mr_t[2:3, :]
    valid = (ml[:, 3:4] > 0.5) & (mr_t[3:4, :] > 0.5)
    mask = (dy <= row_band) & (dx >= 0.0) & (dx <= max_disparity) \
        & same_level & valid
    return jnp.where(mask, dist, BIG)


def first_argmin(dist, best):
    """Lowest column index attaining the row minimum ``best`` ((R, 1))
    of ``dist`` ((R, C) int32): first-occurrence argmin, as (R, 1)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    return jnp.min(jnp.where(dist == best, cols, dist.shape[1]), axis=1,
                   keepdims=True)


def _kernel(dl_ref, ml_ref, dr_ref, mr_ref, dist_ref, idx_ref, *,
            row_band: float, max_disparity: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dist_ref[...] = jnp.full_like(dist_ref, BIG)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    dist = masked_hamming(dl_ref[...], ml_ref[...], dr_ref[...].T,
                          mr_ref[...].T, row_band=row_band,
                          max_disparity=max_disparity)

    # Compare: running argmin against the accumulated best.
    tile_best = jnp.min(dist, axis=1)                      # (BK,)
    tile_arg = jnp.argmin(dist, axis=1).astype(jnp.int32) + j * BM
    improved = tile_best < dist_ref[...]
    idx_ref[...] = jnp.where(improved, tile_arg, idx_ref[...])
    dist_ref[...] = jnp.where(improved, tile_best, dist_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("row_band", "max_disparity", "interpret"))
def hamming_match_pallas(desc_l: jnp.ndarray, meta_l: jnp.ndarray,
                         desc_r: jnp.ndarray, meta_r: jnp.ndarray, *,
                         row_band: float, max_disparity: float,
                         interpret: bool = False):
    """desc_*: (K, 8)/(M, 8) uint32 (K, M multiples of 128 — ops.py pads).
    meta_*: (K, 4)/(M, 4) float32 rows of (x, y, level, valid).
    Returns (best_dist (K,) int32, best_idx (K,) int32); masked-out rows
    keep dist=BIG, idx=-1."""
    k, m = desc_l.shape[0], desc_r.shape[0]
    grid = (k // BK, m // BM)
    kern = functools.partial(_kernel, row_band=float(row_band),
                             max_disparity=float(max_disparity))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BK, 8), lambda i, j: (i, 0)),
            pl.BlockSpec((BK, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((BM, 8), lambda i, j: (j, 0)),
            pl.BlockSpec((BM, 4), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BK,), lambda i, j: (i,)),
            pl.BlockSpec((BK,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k,), jnp.int32),
            jax.ShapeDtypeStruct((k,), jnp.int32),
        ],
        interpret=interpret,
        name="hamming_match_pallas",
    )(desc_l, meta_l, desc_r, meta_r)
