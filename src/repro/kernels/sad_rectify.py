"""Pallas TPU kernel: SAD rectification sweep.

The paper's Correction and Disparity Computing module (Sec. III-D): for
each matched pair, an 11x11 window around the left feature is compared
(sum of absolute differences) against the right window slid over
+-sad_range pixels; the argmin re-locates the right feature.

Layout note (TPU): patch tensors are (BK, P, P) / (BK, P, P + 2R) with
tiny trailing dims — lanes are padded to 128 on real hardware, which is
acceptable because this module is minuscule (the FPGA version used 0
DSPs / 0 BRAMs, Tab. II); correctness and fusion matter, not MXU
utilization.  BK = 128 features per grid step keeps the sublane axis
full.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BK = 128


def _kernel(lp_ref, rs_ref, o_ref, *, patch: int, sweep: int):
    lp = lp_ref[...].astype(jnp.int32)       # (BK, P, P)
    rs = rs_ref[...].astype(jnp.int32)       # (BK, P, P + 2R)
    for s in range(sweep):
        window = rs[:, :, s:s + patch]
        sad = jnp.sum(jnp.abs(lp - window), axis=(1, 2))   # (BK,)
        o_ref[:, s] = sad


@functools.partial(jax.jit, static_argnames=("interpret",))
def sad_search_pallas(left_patches: jnp.ndarray, right_strips: jnp.ndarray,
                      *, interpret: bool = False) -> jnp.ndarray:
    """left_patches: (K, P, P); right_strips: (K, P, P + 2R); K % 128 == 0.
    Returns (K, 2R + 1) int32 SAD table (argmin taken by the caller)."""
    k, p, _ = left_patches.shape
    sweep = right_strips.shape[-1] - p + 1
    kern = functools.partial(_kernel, patch=p, sweep=sweep)
    return pl.pallas_call(
        kern,
        grid=(k // BK,),
        in_specs=[
            pl.BlockSpec((BK, p, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((BK, p, right_strips.shape[-1]),
                         lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((BK, sweep), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, sweep), jnp.int32),
        interpret=interpret,
        name="sad_search_pallas",
    )(left_patches.astype(jnp.int32), right_strips.astype(jnp.int32))
