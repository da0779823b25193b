"""ORB feature extraction — the paper's Feature Extractor block (Fig. 3d)
as a whole-frame dense/sparse pipeline: TWO kernel launches per FRAME.

This is the FE engine under the ``VisualSystem`` session
(``repro.core.pipeline``): the session's ``process_frame`` /
``process_fleet`` / ``extract`` entry points all flatten their camera
(and fleet-rig) axes into the single leading batch axis of
``extract_features_batched`` — all cameras of all rigs enter as one
batch, the pyramid is built, and the entire frame — every camera at
every pyramid level — then costs exactly TWO fused kernel launches:

  1. DENSE stage (``ops.fast_blur_nms_pyramid``): ONE launch whose grid
     walks (camera x level slab, tile).  Ragged level slabs are padded
     to a common tile grid and masked by a per-slab (true_h, true_w)
     table; each VMEM pass emits both the smoothed image (rBRIEF input)
     and the NMS'd FAST score map (top-K input).
  2. SPARSE stage (``ops.orient_describe_pyramid``): after the per-level
     static top-K, ONE launch over the level-sorted (B, K_total)
     keypoint block.  Each (camera, K-block) grid step resolves its
     raw/smoothed slab pair through the static block->level offsets in
     the kernel's index maps and emits orientation theta, the circular-
     patch moments, and the packed 8 x uint32 rBRIEF descriptor, with
     steering resolved through the 30-degree-binned LUT ROM.

This is the TPU analog of the paper's whole-frame streaming FE (Sec.
III-B/III-C): the FPGA streams each frame — all channels, all scales —
once through one shared FAST + smoothing datapath and then feeds
rotation and description from a shared patch buffer.  Earlier revisions
re-launched both stages once per pyramid level (2 x L launches per
frame); that schedule survives as ``extract_features_per_level``, the
oracle the whole-frame path is property-tested against bit-for-bit and
the baseline of the ``table_whole_frame_vs_per_level`` benchmark.  The
single-image ``extract_features`` is a batch-of-one view of the same
whole-frame pipeline.

Per frame: batched pyramid -> one dense launch -> per-level top-K ->
one sparse launch, then merge levels into one static-shape FeatureSet
with level-0 coords.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import fast, pyramid
from repro.core.types import FeatureSet, ORBConfig
from repro.kernels import ops


def _merge_levels(parts: list[FeatureSet]) -> FeatureSet:
    return FeatureSet(*[jnp.concatenate([getattr(p, f) for p in parts],
                                        axis=1)
                        for f in FeatureSet._fields])


def _level_features(lvl: int, cfg: ORBConfig, xy, vals, valid,
                    theta, desc) -> FeatureSet:
    b, k_l = xy.shape[0], xy.shape[1]
    scale = cfg.scale_factor ** lvl
    return FeatureSet(
        xy=xy.astype(jnp.float32) * scale,
        level=jnp.full((b, k_l), lvl, dtype=jnp.int32),
        # int16 scores (the uint8 datapath) cast losslessly: FAST
        # scores live in [0, 255].  FeatureSet dtypes never change.
        score=vals.astype(jnp.float32),
        theta=theta,
        desc=desc,
        valid=valid,
    )


def extract_features_batched(images: jnp.ndarray, cfg: ORBConfig,
                             impl: str | None = None, *,
                             precision: str = "f32") -> FeatureSet:
    """images: (B, H, W) uint8/float in [0, 255] — B cameras — to a
    FeatureSet of K features with a leading (B,) axis on every field.

    Exactly 2 kernel launches per FRAME (1 dense + 1 sparse) for ALL
    cameras x ALL pyramid levels — asserted by the traced launch counter
    in tests and gated in CI by ``benchmarks.check_launches``.

    precision="uint8" keeps the pyramid slabs uint8 end-to-end (4x less
    resident VMEM, int32 accumulators in the kernels — paper Sec. III
    word-length optimization); the FeatureSet dtypes are unchanged, and
    on quantized images the keypoints/descriptors are bit-equal to the
    f32 path (pinned in tests/test_precision.py).
    """
    levels = pyramid.build_pyramid_batched(images, cfg,
                                           precision=precision)
    ks = cfg.features_per_level()
    with jax.named_scope("dense_fe"):
        dense = ops.fast_blur_nms_pyramid(
            levels, float(cfg.fast_threshold), nms=cfg.nms,
            quantized=cfg.quantized, impl=impl)
    topk = []
    for (_smoothed, score), k_l in zip(dense, ks):
        topk.append(jax.vmap(
            lambda s, k=k_l: fast.select_topk(s, k, cfg.border))(score))
    with jax.named_scope("describe"):
        sparse = ops.orient_describe_pyramid(
            levels, [sm for sm, _ in dense], [xy for xy, _, _ in topk],
            impl=impl)
        parts = []
        for lvl, ((xy, vals, valid), (theta, _mom, desc)) in enumerate(
                zip(topk, sparse)):
            parts.append(_level_features(lvl, cfg, xy, vals, valid, theta,
                                         desc))
        return _merge_levels(parts)


def extract_features_per_level(images: jnp.ndarray, cfg: ORBConfig,
                               impl: str | None = None, *,
                               precision: str = "f32") -> FeatureSet:
    """Reference per-level schedule: 2 launches per pyramid LEVEL (the
    PR-2 pipeline).  Kept as the oracle the whole-frame path is pinned
    against bit-for-bit (``tests/test_whole_frame_fused.py``) and as the
    baseline of the ``table_whole_frame_vs_per_level`` benchmark; the
    hot path is ``extract_features_batched``.
    """
    levels = pyramid.build_pyramid_batched(images, cfg,
                                           precision=precision)
    ks = cfg.features_per_level()
    parts = []
    for lvl, (imgs_l, k_l) in enumerate(zip(levels, ks)):
        smoothed, score = ops.fast_blur_nms_batched(
            imgs_l, float(cfg.fast_threshold), nms=cfg.nms,
            quantized=cfg.quantized, impl=impl)
        xy, vals, valid = jax.vmap(
            lambda s: fast.select_topk(s, k_l, cfg.border))(score)
        theta, _moments, desc = ops.orient_describe_batched(
            imgs_l, smoothed, xy, impl=impl)
        parts.append(_level_features(lvl, cfg, xy, vals, valid, theta, desc))
    return _merge_levels(parts)


def extract_features(image: jnp.ndarray, cfg: ORBConfig,
                     impl: str | None = None, *,
                     precision: str = "f32") -> FeatureSet:
    """image: (H, W) uint8/float in [0, 255] -> FeatureSet of K features.

    Batch-of-one view of ``extract_features_batched`` so single-image
    callers share the whole-frame fused kernel path bit-for-bit.
    """
    feats = extract_features_batched(image[None], cfg, impl=impl,
                                     precision=precision)
    return jax.tree.map(lambda x: x[0], feats)
