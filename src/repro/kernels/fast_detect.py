"""Pallas TPU kernel: FAST-9/16 corner score map.

TPU adaptation of the paper's FAST Detection module (Sec. III-C).  The
FPGA streams the image through line buffers and register banks; here the
image is cut into full-width row bands with a 3-pixel halo
(``frontend_fused.row_band_spec``); each grid step slices its
(TILE_H + 6, TILE_W + 6) window, which gives the overlapping halo the
Bresenham-16 circle needs, and the 16 taps become static VREG shifts of
the tile — the register-bank analog.  Default 128x128 output tiles,
MXU-free, pure VPU stencil.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.frontend_fused import halo_window, row_band_spec

from repro.kernels.ref import ARC_LEN, CIRCLE16

TILE_H = 128
TILE_W = 128
HALO = 3


def _kernel(x_ref, o_ref, *, threshold: float, tile_h: int, tile_w: int):
    x = halo_window(x_ref, HALO, tile_w)           # (tile_h+6, tile_w+6)
    center = x[HALO:HALO + tile_h, HALO:HALO + tile_w]
    # 16 circle taps as static shifted views of the halo'd tile.
    taps = [
        x[HALO + dy:HALO + dy + tile_h, HALO + dx:HALO + dx + tile_w] - center
        for dx, dy in CIRCLE16
    ]
    # Arc mins/maxes over 9 contiguous taps (16 wrap-around windows),
    # unrolled with running min/max to bound live registers.
    score_bright = None
    score_dark = None
    for s in range(16):
        arc_min = taps[s % 16]
        arc_max = taps[s % 16]
        for j in range(1, ARC_LEN):
            t = taps[(s + j) % 16]
            arc_min = jnp.minimum(arc_min, t)
            arc_max = jnp.maximum(arc_max, t)
        score_bright = arc_min if score_bright is None else jnp.maximum(
            score_bright, arc_min)
        score_dark = arc_max if score_dark is None else jnp.minimum(
            score_dark, arc_max)
    score = jnp.maximum(score_bright, -score_dark)
    o_ref[...] = jnp.where(score > threshold, score, 0.0)


@functools.partial(jax.jit, static_argnames=("threshold", "interpret"))
def fast_score_map_pallas(padded: jnp.ndarray, *, threshold: float,
                          interpret: bool = False) -> jnp.ndarray:
    """padded: (H + 6, W + 6) float32, edge-padded by 3 and tile-aligned
    (H % TILE_H == 0, W % TILE_W == 0 — ``ops.py`` guarantees this).
    Returns (H, W) float32 score map."""
    h = padded.shape[0] - 2 * HALO
    w = padded.shape[1] - 2 * HALO
    grid = (h // TILE_H, w // TILE_W)
    kern = functools.partial(_kernel, threshold=float(threshold),
                             tile_h=TILE_H, tile_w=TILE_W)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[row_band_spec(TILE_H + 2 * HALO, w + 2 * HALO, False,
                                TILE_H)],
        out_specs=pl.BlockSpec((TILE_H, TILE_W), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.float32),
        interpret=interpret,
        name="fast_score_map_pallas",
    )(padded.astype(jnp.float32))
