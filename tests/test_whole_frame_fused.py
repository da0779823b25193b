"""Whole-frame fused extraction vs the per-level oracle pipeline.

The whole-frame schedule (ONE dense + ONE sparse launch per frame for
all cameras x all pyramid levels) must be BIT-exact against the
per-level pipeline (``orb.extract_features_per_level`` — 2 launches per
level) on every FeatureSet field, on both the jnp fallback and the
Pallas interpret path, for ragged/odd level shapes, boundary keypoints
and all-invalid levels.  A traced launch-count assertion pins the
2-launch budget (3 for a full quad frame with the fused FM).

Deterministic parametrized pins run everywhere; the Hypothesis property
suite (random camera counts, shapes, level counts, thresholds) runs
where hypothesis is installed (CI) under the fixed-seed profile from
``conftest.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CameraIntrinsics, ORBConfig, PipelineConfig,
                        RigConfig, VisualSystem,
                        extract_features_batched, extract_features_per_level)
from repro.core import pyramid
from repro.kernels import ops

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # dev-only dep; property tests skip
    HAVE_HYPOTHESIS = False


# Both schedules as one jitted program per (shape, config), as a session
# runs them, instead of op-by-op dispatch: the sweeps below are
# dominated by compilation.
_STATIC = ("cfg", "impl")
_whole = jax.jit(extract_features_batched, static_argnames=_STATIC)
_per_level = jax.jit(extract_features_per_level, static_argnames=_STATIC)


def _imgs(seed, b, h, w):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, 256, (b, h, w)).astype(np.float32))


def _assert_featureset_equal(a, b, msg=""):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg} field {f}")


def _levels(seed, b, shapes):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randint(0, 256, (b, h, w)).astype(np.float32))
            for h, w in shapes]


# ---------------------------------------------------------------------------
# Dense stage: ops.fast_blur_nms_pyramid vs per-level dispatch.

RAGGED = [(70, 111), (58, 93), (37, 53)]       # non-square, odd, < 1 tile


@pytest.mark.parametrize("nms", [True, False])
@pytest.mark.parametrize("quantized", [True, False])
def test_dense_pyramid_bitexact_vs_per_level(nms, quantized):
    levels = _levels(3, 2, RAGGED)
    for impl in ("ref", "pallas"):
        outs = ops.fast_blur_nms_pyramid(levels, 20.0, nms=nms,
                                         quantized=quantized, impl=impl)
        assert len(outs) == len(levels)
        for lvl, (lv, (blur, score)) in enumerate(zip(levels, outs)):
            want_b, want_s = ops.fast_blur_nms_batched(
                lv, 20.0, nms=nms, quantized=quantized, impl="ref")
            if impl == "pallas" and not quantized:
                # float blur divides inside the kernel: last-ulp drift vs
                # the jnp oracle — same tolerance as the per-level
                # test_fused_flag_combinations; quantized (the pipeline
                # default) is bit-exact.
                np.testing.assert_allclose(
                    np.asarray(blur), np.asarray(want_b), rtol=1e-5,
                    atol=1e-4, err_msg=f"{impl} blur level {lvl}")
            else:
                np.testing.assert_array_equal(
                    np.asarray(blur), np.asarray(want_b),
                    err_msg=f"{impl} blur level {lvl}")
            np.testing.assert_array_equal(
                np.asarray(score), np.asarray(want_s),
                err_msg=f"{impl} score level {lvl}")


@pytest.mark.parametrize("nms", [True, False])
@pytest.mark.parametrize("quantized", [True, False])
def test_dense_pyramid_stacked_jnp_oracle_bitexact(nms, quantized):
    """The stacked jnp mirror of the kernel's ragged-padding semantics
    (ONE pass over the common canvas + true-shape masking) must be
    bit-exact against the per-level fallback — an independent pin of the
    padding logic that doesn't go through Pallas interpret mode."""
    levels = _levels(13, 2, RAGGED)
    outs = ops.fast_blur_nms_pyramid_stacked_jnp(
        levels, 20.0, nms=nms, quantized=quantized)
    for lvl, (lv, (blur, score)) in enumerate(zip(levels, outs)):
        want_b, want_s = ops.fast_blur_nms_batched(
            lv, 20.0, nms=nms, quantized=quantized, impl="ref")
        np.testing.assert_array_equal(np.asarray(blur), np.asarray(want_b),
                                      err_msg=f"blur level {lvl}")
        np.testing.assert_array_equal(np.asarray(score),
                                      np.asarray(want_s),
                                      err_msg=f"score level {lvl}")


def test_dense_pyramid_single_level_degenerates_to_batched():
    levels = _levels(4, 3, [(96, 128)])
    for impl in ("ref", "pallas"):
        (blur, score), = ops.fast_blur_nms_pyramid(levels, 15.0, impl=impl)
        want_b, want_s = ops.fast_blur_nms_batched(levels[0], 15.0,
                                                   impl=impl)
        np.testing.assert_array_equal(np.asarray(blur), np.asarray(want_b))
        np.testing.assert_array_equal(np.asarray(score), np.asarray(want_s))


def test_dense_pyramid_corner_on_small_level_boundary():
    """A corner on the last row/col of the SMALLEST level must survive:
    its NMS neighbours are the -1 mask sentinels of the common-canvas
    padding, never edge-replicated garbage from the bigger canvas."""
    shapes = [(130, 131), (66, 67)]
    levels = []
    for h, w in shapes:
        img = np.full((1, h, w), 10.0, np.float32)
        img[:, h - 6:, w - 6:] = 220.0
        levels.append(jnp.asarray(img))
    out_ref = ops.fast_blur_nms_pyramid(levels, 20.0, impl="ref")
    out_pl = ops.fast_blur_nms_pyramid(levels, 20.0, impl="pallas")
    for (br, sr), (bp, sp) in zip(out_ref, out_pl):
        np.testing.assert_array_equal(np.asarray(sr), np.asarray(sp))
        np.testing.assert_array_equal(np.asarray(br), np.asarray(bp))
        assert float(jnp.sum(sr > 0)) > 0


# ---------------------------------------------------------------------------
# Sparse stage: ops.orient_describe_pyramid vs per-level dispatch.

def test_sparse_pyramid_bitexact_vs_per_level():
    levels = _levels(5, 2, RAGGED)
    sms = [ops.fast_blur_nms_batched(lv, 20.0, impl="ref")[0]
           for lv in levels]
    rng = np.random.RandomState(6)
    # K not a KP_BLOCK multiple, and coords spanning borders AND
    # out-of-range values (top-K padding rows carry arbitrary coords)
    xys = []
    for lv, k in zip(levels, (21, 8, 5)):
        h, w = lv.shape[1], lv.shape[2]
        xy = np.stack([rng.randint(-7, w + 7, (2, k)),
                       rng.randint(-7, h + 7, (2, k))], -1)
        xy[:, 0] = [0, 0]
        xy[:, -1] = [w - 1, h - 1]
        xys.append(jnp.asarray(xy.astype(np.int32)))
    out_ref = ops.orient_describe_pyramid(levels, sms, xys, impl="ref")
    out_pl = ops.orient_describe_pyramid(levels, sms, xys, impl="pallas")
    for lvl, (lv, sm, xy) in enumerate(zip(levels, sms, xys)):
        want = ops.orient_describe_batched(lv, sm, xy, impl="ref")
        for name, a, b, c in zip(("theta", "moments", "desc"),
                                 out_ref[lvl], out_pl[lvl], want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                          err_msg=f"ref {name} lvl {lvl}")
            np.testing.assert_array_equal(np.asarray(b), np.asarray(c),
                                          err_msg=f"pallas {name} lvl {lvl}")
            assert np.isfinite(np.asarray(a)).all() or name == "desc"


# ---------------------------------------------------------------------------
# Full extractor: whole-frame vs per-level pipeline.

@pytest.mark.parametrize("b,shape,n_levels", [
    (1, (64, 96), 1),
    (2, (70, 111), 3),       # odd ragged shapes
    (4, (96, 128), 2),       # the quad rig
    (3, (37, 53), 5),        # image smaller than one dense tile, deep
])
def test_whole_frame_extractor_equals_per_level_ref(b, shape, n_levels):
    imgs = _imgs(7, b, *shape)
    cfg = ORBConfig(height=shape[0], width=shape[1], max_features=48,
                    n_levels=n_levels)
    whole = _whole(imgs, cfg, impl="ref")
    per = _per_level(imgs, cfg, impl="ref")
    _assert_featureset_equal(whole, per, f"ref b={b} {shape} L={n_levels}")


@pytest.mark.parametrize("b,shape,n_levels", [
    (2, (70, 111), 2),
    (4, (64, 96), 2),
])
def test_whole_frame_extractor_equals_per_level_pallas(b, shape, n_levels):
    imgs = _imgs(8, b, *shape)
    cfg = ORBConfig(height=shape[0], width=shape[1], max_features=32,
                    n_levels=n_levels)
    whole = _whole(imgs, cfg, impl="pallas")
    per = _per_level(imgs, cfg, impl="pallas")
    _assert_featureset_equal(whole, per, "pallas whole vs per-level")
    _assert_featureset_equal(whole, _whole(imgs, cfg, impl="ref"),
                             "pallas vs ref")


def test_whole_frame_paper_level1_shape():
    """600x1067 — the paper's 1280x720 level-1 shape, far from tile
    alignment on both axes — through the WHOLE-frame pallas path with a
    second ragged level (500x889)."""
    cfg = ORBConfig(height=600, width=1067, n_levels=2, max_features=64)
    shapes = pyramid.level_shapes(cfg)
    assert shapes == [(600, 1067), (500, 889)]
    levels = _levels(9, 1, shapes)
    out_ref = ops.fast_blur_nms_pyramid(levels, 20.0, impl="ref")
    out_pl = ops.fast_blur_nms_pyramid(levels, 20.0, impl="pallas")
    for lvl, ((br, sr), (bp, sp)) in enumerate(zip(out_ref, out_pl)):
        np.testing.assert_array_equal(np.asarray(br), np.asarray(bp),
                                      err_msg=f"blur level {lvl}")
        np.testing.assert_array_equal(np.asarray(sr), np.asarray(sp),
                                      err_msg=f"score level {lvl}")
    rng = np.random.RandomState(10)
    xys = [jnp.asarray(np.stack([rng.randint(0, w, (1, 9)),
                                 rng.randint(0, h, (1, 9))], -1)
                       .astype(np.int32))
           for h, w in shapes]
    sms = [blur for blur, _ in out_ref]
    sp_ref = ops.orient_describe_pyramid(levels, sms, xys, impl="ref")
    sp_pl = ops.orient_describe_pyramid(levels, sms, xys, impl="pallas")
    for lvl, (a, b) in enumerate(zip(sp_ref, sp_pl)):
        for name, x, y in zip(("theta", "moments", "desc"), a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{name} level {lvl}")


def test_whole_frame_all_invalid_levels():
    """Blank images: no corners anywhere — every level's top-K emits
    valid=False rows with degenerate coords; the whole-frame sparse
    launch must stay finite and agree across impls and schedules."""
    imgs = jnp.zeros((2, 64, 96), jnp.float32)
    cfg = ORBConfig(height=64, width=96, max_features=16, n_levels=3)
    outs = {}
    for impl in ("ref", "pallas"):
        feats = extract_features_batched(imgs, cfg, impl=impl)
        assert int(feats.count()) == 0
        assert np.isfinite(np.asarray(feats.theta)).all()
        outs[impl] = feats
    _assert_featureset_equal(outs["ref"], outs["pallas"], "all-invalid")
    _assert_featureset_equal(outs["ref"],
                             extract_features_per_level(imgs, cfg,
                                                        impl="ref"),
                             "all-invalid vs per-level")


# ---------------------------------------------------------------------------
# Launch budget: the acceptance number of this refactor.

def test_whole_frame_two_fe_launches():
    """Acceptance: a traced frame costs exactly 2 FE launches (1 dense +
    1 sparse) regardless of camera count and level count, and a traced
    quad frame costs exactly 3 kernel launches total (+ the single
    fused FM launch covering both pairs)."""
    for b, n_levels in ((1, 1), (2, 3), (4, 2)):
        imgs = _imgs(11, b, 64, 96)
        cfg = ORBConfig(height=64, width=96, max_features=16,
                        n_levels=n_levels)
        with ops.launch_audit() as audit:
            jax.eval_shape(
                lambda im: extract_features_batched(im, cfg,
                                                    impl="pallas"),
                imgs)
        assert audit.count == 2, (b, n_levels, audit.count)
    cfg = ORBConfig(height=64, width=96, max_features=16, n_levels=2,
                    max_disparity=32)
    intr = CameraIntrinsics(cx=48.0, cy=32.0)
    vs = VisualSystem(RigConfig.quad(intr), PipelineConfig(orb=cfg))
    assert vs.traced_launches("process_frame", _imgs(12, 4, 64, 96)) == 3


# ---------------------------------------------------------------------------
# Hypothesis property suite (runs where hypothesis is installed — CI).

if HAVE_HYPOTHESIS:

    @given(b=st.integers(1, 4), h=st.integers(24, 96),
           w=st.integers(24, 96), n_levels=st.integers(1, 8),
           thr=st.floats(5.0, 40.0), seed=st.integers(0, 2**16))
    @settings(max_examples=20, deadline=None)
    def test_prop_whole_frame_equals_per_level_ref(b, h, w, n_levels,
                                                   thr, seed):
        """Full-pipeline property: for random camera counts, odd shapes
        and level counts, the whole-frame jnp path is bit-exact against
        the per-level pipeline on every field."""
        imgs = _imgs(seed, b, h, w)
        cfg = ORBConfig(height=h, width=w, max_features=24,
                        n_levels=n_levels, fast_threshold=int(thr))
        whole = _whole(imgs, cfg, impl="ref")
        per = _per_level(imgs, cfg, impl="ref")
        _assert_featureset_equal(whole, per,
                                 f"b={b} {h}x{w} L={n_levels} thr={thr}")

    @given(b=st.integers(1, 2), h=st.integers(16, 72),
           w=st.integers(16, 72), n_levels=st.integers(1, 4),
           seed=st.integers(0, 2**16))
    @settings(max_examples=10, deadline=None)
    def test_prop_dense_pyramid_pallas_bitexact(b, h, w, n_levels, seed):
        """Dense whole-pyramid Pallas launch (interpret mode) vs the
        per-level jnp oracle, random ragged shapes."""
        cfg = ORBConfig(height=h, width=w, n_levels=n_levels)
        levels = _levels(seed, b, pyramid.level_shapes(cfg))
        outs = ops.fast_blur_nms_pyramid(levels, 20.0, impl="pallas")
        for lvl, (lv, (blur, score)) in enumerate(zip(levels, outs)):
            want_b, want_s = ops.fast_blur_nms_batched(lv, 20.0,
                                                       impl="ref")
            np.testing.assert_array_equal(np.asarray(blur),
                                          np.asarray(want_b),
                                          err_msg=f"blur lvl {lvl}")
            np.testing.assert_array_equal(np.asarray(score),
                                          np.asarray(want_s),
                                          err_msg=f"score lvl {lvl}")

    @given(b=st.integers(1, 2), h=st.integers(16, 72),
           w=st.integers(16, 72), n_levels=st.integers(1, 3),
           k=st.integers(1, 20), seed=st.integers(0, 2**16))
    @settings(max_examples=10, deadline=None)
    def test_prop_sparse_pyramid_pallas_bitexact(b, h, w, n_levels, k,
                                                 seed):
        """Sparse whole-frame Pallas launch (interpret mode) vs the
        per-level oracle, with keypoints spanning borders and
        out-of-range coords (boundary clamping)."""
        cfg = ORBConfig(height=h, width=w, n_levels=n_levels)
        shapes = pyramid.level_shapes(cfg)
        levels = _levels(seed, b, shapes)
        sms = [ops.fast_blur_nms_batched(lv, 20.0, impl="ref")[0]
               for lv in levels]
        rng = np.random.RandomState(seed)
        xys = [jnp.asarray(np.stack(
            [rng.randint(-10, w_l + 10, (b, k)),
             rng.randint(-10, h_l + 10, (b, k))], -1).astype(np.int32))
            for h_l, w_l in shapes]
        got = ops.orient_describe_pyramid(levels, sms, xys, impl="pallas")
        for lvl in range(n_levels):
            want = ops.orient_describe_batched(levels[lvl], sms[lvl],
                                               xys[lvl], impl="ref")
            for name, a, c in zip(("theta", "moments", "desc"),
                                  got[lvl], want):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(c),
                    err_msg=f"{name} lvl {lvl}")
