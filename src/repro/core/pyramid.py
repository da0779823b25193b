"""Image pyramid (paper Sec. III-C, "Image Resizing").

Two-layer pyramid with bilinear interpolation; 1280x720 -> 1067x600 at
the paper's 1.2 scale factor.  Works on float32 images in [0, 255]; the
quantized path rounds back to uint8 levels, matching the FPGA's 8-bit
datapath.

The batched pyramid feeds the whole-frame fused frontend: every level
of every camera goes into ONE dense kernel launch
(``ops.fast_blur_nms_pyramid``), which pads the ragged level shapes
returned by ``level_shapes`` to a common tile grid and masks by true
shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import ORBConfig


def bilinear_resize(image: jnp.ndarray, out_hw: tuple[int, int]) -> jnp.ndarray:
    """Bilinear resize of a single-channel image (H, W) -> out_hw."""
    img = image.astype(jnp.float32)
    return jax.image.resize(img, out_hw, method="bilinear")


def build_pyramid(image: jnp.ndarray, cfg: ORBConfig, *,
                  precision: str = "f32") -> list[jnp.ndarray]:
    """Return ``cfg.n_levels`` level images; level 0 is the input.

    precision="f32" (default) emits float32 levels as always.
    precision="uint8" emits uint8 levels — the paper's 8-bit datapath:
    level 0 is the uint8 input unchanged, and each resize runs bilinear
    in f32 then rounds/clips back to uint8.  Because the f32 path with
    ``cfg.quantized`` already rounds+clips every resized level to
    integer values in [0, 255], the uint8 levels are the SAME values in
    a 4x smaller slab."""
    if precision == "uint8":
        levels = [image.astype(jnp.uint8)]
        for lvl in range(1, cfg.n_levels):
            out = bilinear_resize(levels[-1], cfg.level_shape(lvl))
            levels.append(jnp.round(jnp.clip(out, 0.0, 255.0))
                          .astype(jnp.uint8))
        return levels
    img = image.astype(jnp.float32)
    levels = [img]
    for lvl in range(1, cfg.n_levels):
        out = bilinear_resize(levels[-1], cfg.level_shape(lvl))
        if cfg.quantized:
            out = jnp.round(jnp.clip(out, 0.0, 255.0))
        levels.append(out)
    return levels


def level_shapes(cfg: ORBConfig) -> list[tuple[int, int]]:
    """Static (h, w) of every pyramid level — the ragged shapes the
    whole-frame launch pads to a common tile grid."""
    return [cfg.level_shape(lvl) for lvl in range(cfg.n_levels)]


@jax.named_scope("pyramid")
def build_pyramid_batched(images: jnp.ndarray, cfg: ORBConfig, *,
                          precision: str = "f32") -> list[jnp.ndarray]:
    """Batched pyramid: (B, H, W) -> list of (B, h_l, w_l) level images
    (float32, or uint8 under precision="uint8").

    B is the flattened camera batch of the fused frontend; each level is
    one resize over the whole batch.  All levels together feed ONE
    whole-frame dense launch (``ops.fast_blur_nms_pyramid``).
    """
    return jax.vmap(
        lambda im: build_pyramid(im, cfg, precision=precision))(images)
