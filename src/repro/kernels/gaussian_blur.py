"""Pallas TPU kernel: separable 7x7 Gaussian smoothing.

The paper's Image Smoothing module (Sec. III-C) streams 7x7 patches
through two-stage shifting line buffers fused with the descriptor
pipeline.  The TPU analog: one halo'd VMEM tile per grid cell, the two
1-D passes fused in a single kernel so the horizontal intermediate never
leaves VMEM (the line-buffer role).

Integer-weight taps ([1,4,8,10,8,4,1], norm 36) implement the paper's
8-bit word-length optimization; the quantized path rounds once at the
end and is bit-exact against the ``ref.py`` oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.frontend_fused import halo_window, row_band_spec

from repro.kernels.ref import GAUSS7_NORM, GAUSS7_WEIGHTS_INT

TILE_H = 128
TILE_W = 128
HALO = 3


def _kernel(x_ref, o_ref, *, quantized: bool, tile_h: int, tile_w: int):
    x = halo_window(x_ref, HALO, tile_w)        # (tile_h+6, tile_w+6) f32
    w = [float(v) for v in GAUSS7_WEIGHTS_INT]
    # Horizontal pass on the full halo'd tile (keeps vertical halo rows).
    horiz = None
    for k in range(7):
        term = w[k] * x[:, k:k + tile_w]
        horiz = term if horiz is None else horiz + term    # (tile_h+6, tile_w)
    # Vertical pass.
    vert = None
    for k in range(7):
        term = w[k] * horiz[k:k + tile_h, :]
        vert = term if vert is None else vert + term       # (tile_h, tile_w)
    if quantized:
        norm2 = float(GAUSS7_NORM * GAUSS7_NORM)
        o_ref[...] = jnp.floor((vert + norm2 / 2.0) / norm2)
    else:
        o_ref[...] = vert / float(GAUSS7_NORM * GAUSS7_NORM)


@functools.partial(jax.jit, static_argnames=("quantized", "interpret"))
def gaussian_blur7_pallas(padded: jnp.ndarray, *, quantized: bool = True,
                          interpret: bool = False) -> jnp.ndarray:
    """padded: (H + 6, W + 6) float32, edge-padded by 3, tile-aligned.
    Returns (H, W) float32 smoothed image."""
    h = padded.shape[0] - 2 * HALO
    w = padded.shape[1] - 2 * HALO
    grid = (h // TILE_H, w // TILE_W)
    kern = functools.partial(_kernel, quantized=quantized,
                             tile_h=TILE_H, tile_w=TILE_W)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[row_band_spec(TILE_H + 2 * HALO, w + 2 * HALO, False,
                                TILE_H)],
        out_specs=pl.BlockSpec((TILE_H, TILE_W), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.float32),
        interpret=interpret,
        name="gaussian_blur7_pallas",
    )(padded.astype(jnp.float32))
