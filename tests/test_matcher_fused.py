"""Fused Feature Matcher megakernel vs the unfused two-kernel + gather
oracle path.

``match_pair_fused`` (ONE Pallas launch per frame: Hamming match + SAD
rectification with in-kernel patch reads, pair axis folded into the
grid) must be BIT-exact against ``match_pair_unfused`` (the retained
``hamming_match`` kernel + host-graph ``_gather_patches`` +
``sad_search`` kernel schedule) on every MatchSet/DepthSet field, on
both the jnp fallback and the Pallas interpret path — including 640x480
and odd shapes, all-invalid features and argmin ties.  The
``_gather_patches`` border clamp is audited against a python-loop
per-pixel oracle (``ref.gather_patches_bruteforce``), and a traced
``VisualSystem.process_frame`` pins the 3-launch budget (2 FE + 1 FM).

Deterministic parametrized pins run everywhere; the Hypothesis property
suite (random K/M/pair counts) runs where hypothesis is installed (CI)
under the fixed-seed profile from ``conftest.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CameraIntrinsics, FeatureSet, ORBConfig,
                        PipelineConfig, RigConfig, VisualSystem,
                        match_pair_fused, match_pair_unfused,
                        sad_rectify_unfused, stereo_match_unfused)
from repro.core.matching import _gather_patches
from repro.kernels import ops, ref

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # dev-only dep; property tests skip
    HAVE_HYPOTHESIS = False


# Both FM schedules as one jitted program per (shape, config) instead
# of op-by-op dispatch: these sweeps are dominated by compilation.
_STATIC = ("cfg", "intr", "impl")
_fused = jax.jit(match_pair_fused, static_argnames=_STATIC)
_unfused = jax.jit(match_pair_unfused, static_argnames=_STATIC)


def _system(cfg, intr=None, impl=None):
    intr = intr if intr is not None else CameraIntrinsics()
    return VisualSystem(RigConfig.stereo(intr),
                        PipelineConfig(orb=cfg, impl=impl))


def _random_features(rng, k, h, w, n_levels=2, valid_frac=0.8):
    desc = jnp.asarray(rng.randint(0, 2**32, (k, 8), dtype=np.uint64)
                       .astype(np.uint32))
    return FeatureSet(
        xy=jnp.asarray(np.stack([rng.uniform(-6, w + 6, k),
                                 rng.uniform(-6, h + 6, k)], 1)
                       .astype(np.float32)),
        level=jnp.asarray(rng.randint(0, n_levels, k).astype(np.int32)),
        score=jnp.asarray(rng.uniform(1, 50, k).astype(np.float32)),
        theta=jnp.asarray(rng.uniform(-np.pi, np.pi, k)
                          .astype(np.float32)),
        desc=desc,
        valid=jnp.asarray(rng.uniform(size=k) > 1.0 - valid_frac),
    )


def _stack_feats(feats):
    return jax.tree.map(lambda *x: jnp.stack(x), *feats)


def _pair_inputs(seed, n_pairs, k, m, h, w, valid_frac=0.8):
    rng = np.random.RandomState(seed)
    imgs_l = jnp.asarray(rng.randint(0, 256, (n_pairs, h, w))
                         .astype(np.float32))
    imgs_r = jnp.asarray(rng.randint(0, 256, (n_pairs, h, w))
                         .astype(np.float32))
    fls = [_random_features(rng, k, h, w, valid_frac=valid_frac)
           for _ in range(n_pairs)]
    frs = [_random_features(rng, m, h, w, valid_frac=valid_frac)
           for _ in range(n_pairs)]
    return imgs_l, imgs_r, fls, frs


def _assert_pair_equal(got, want_per_pair, msg=""):
    """got: pair-batched NamedTuple; want_per_pair: list of unbatched."""
    for p, want in enumerate(want_per_pair):
        for f in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f))[p],
                np.asarray(getattr(want, f)),
                err_msg=f"{msg} pair {p} field {f}")


# ---------------------------------------------------------------------------
# Tentpole: fused megakernel vs unfused oracle, bit-for-bit.

@pytest.mark.parametrize("h,w,k,m,n_pairs", [
    (480, 640, 75, 61, 2),       # the paper benchmark resolution
    (97, 143, 37, 29, 2),        # odd shape, far from tile alignment
    (63, 89, 21, 45, 3),         # M > K, three pairs
    (50, 71, 9, 5, 1),           # tiny: K < FM_BK after padding
])
def test_fused_matches_unfused_bitexact(h, w, k, m, n_pairs):
    imgs_l, imgs_r, fls, frs = _pair_inputs(21, n_pairs, k, m, h, w)
    cfg = ORBConfig(height=h, width=w, row_band=25, max_disparity=250,
                    max_hamming=140)
    intr = CameraIntrinsics(fx=120.0, cx=w / 2.0, cy=h / 2.0,
                            baseline=0.2)
    want = [_unfused(imgs_l[p], imgs_r[p], fls[p], frs[p],
                               cfg, intr, impl="ref")
            for p in range(n_pairs)]
    for impl in ("ref", "pallas"):
        mf, df = _fused(imgs_l, imgs_r, _stack_feats(fls),
                                  _stack_feats(frs), cfg, intr,
                                  impl=impl)
        _assert_pair_equal(mf, [wm for wm, _ in want], f"{impl} match")
        _assert_pair_equal(df, [wd for _, wd in want], f"{impl} depth")
    # the scenario must exercise both accepted and rejected matches
    assert any(bool(wm.valid.any()) for wm, _ in want)
    assert any(bool((~wm.valid).any()) for wm, _ in want)


def test_fused_all_invalid_features():
    """Every feature masked out: no candidate anywhere — dist stays at
    the BIG sentinel, indices resolve to 0, the SAD stage reads the
    right-feature-0 fallback window, and fused == unfused still holds
    bit-for-bit on every field."""
    imgs_l, imgs_r, fls, frs = _pair_inputs(22, 2, 17, 13, 64, 96,
                                            valid_frac=0.0)
    cfg = ORBConfig(height=64, width=96, max_disparity=64)
    intr = CameraIntrinsics(cx=48.0, cy=32.0)
    want = [_unfused(imgs_l[p], imgs_r[p], fls[p], frs[p],
                               cfg, intr, impl="ref") for p in range(2)]
    for impl in ("ref", "pallas"):
        mf, df = _fused(imgs_l, imgs_r, _stack_feats(fls),
                                  _stack_feats(frs), cfg, intr,
                                  impl=impl)
        assert int(mf.valid.sum()) == 0
        assert (np.asarray(mf.distance) == ref.MATCH_BIG).all()
        assert (np.asarray(mf.right_index) == 0).all()
        _assert_pair_equal(mf, [wm for wm, _ in want], f"{impl} match")
        _assert_pair_equal(df, [wd for _, wd in want], f"{impl} depth")


def test_fused_tie_breaks_to_lowest_right_index():
    """Identical descriptors planted at several right indices inside the
    search region: the running argmin must resolve to the LOWEST right
    index, across M-tile boundaries, on both impls — the oracle's
    first-occurrence argmin."""
    h, w = 64, 400
    rng = np.random.RandomState(23)
    k, m = 8, 300                       # m spans 3 M-tiles of 128
    fl = _random_features(rng, k, h, w, n_levels=1, valid_frac=1.0)
    fr = _random_features(rng, m, h, w, n_levels=1, valid_frac=1.0)
    # all right features inside every left feature's search region
    fl = fl._replace(xy=jnp.asarray(np.tile([350.0, 30.0], (k, 1))
                                    .astype(np.float32)))
    fr = fr._replace(xy=jnp.asarray(np.tile([200.0, 30.0], (m, 1))
                                    .astype(np.float32)))
    # plant the SAME descriptor as left row 0 at ties spanning tiles
    ties = [5, 120, 129, 250]
    desc_r = np.asarray(fr.desc).copy()
    desc_r[ties] = np.asarray(fl.desc)[0]
    fr = fr._replace(desc=jnp.asarray(desc_r))
    cfg = ORBConfig(height=h, width=w, row_band=100, max_disparity=300,
                    max_hamming=256)
    for impl in ("ref", "pallas"):
        got = _system(cfg, impl=impl).stereo_match(fl, fr)
        want = stereo_match_unfused(fl, fr, cfg, impl="ref")
        np.testing.assert_array_equal(np.asarray(got.right_index),
                                      np.asarray(want.right_index),
                                      err_msg=impl)
        assert int(got.right_index[0]) == ties[0], impl
        assert int(got.distance[0]) == 0, impl


def test_stereo_match_fused_equals_unfused():
    rng_shapes = [(37, 29), (128, 128), (5, 200)]
    cfg = ORBConfig(height=96, width=144, row_band=30, max_disparity=200,
                    max_hamming=200)
    for seed, (k, m) in enumerate(rng_shapes):
        rng = np.random.RandomState(31 + seed)
        fl = _random_features(rng, k, 96, 144)
        fr = _random_features(rng, m, 96, 144)
        want = stereo_match_unfused(fl, fr, cfg, impl="ref")
        for impl in ("ref", "pallas"):
            got = _system(cfg, impl=impl).stereo_match(fl, fr)
            for f in want._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(got, f)),
                    np.asarray(getattr(want, f)),
                    err_msg=f"{impl} K={k} M={m} field {f}")


def test_sad_rectify_in_kernel_equals_unfused():
    """The standalone ``sad_rectify`` (in-kernel patch reads via
    ``ops.sad_patch_search``) vs the retained gather + ``sad_search``
    path, with matches pointing at masked right features (index-0
    fallback) and windows overhanging every border."""
    h, w = 97, 143
    rng = np.random.RandomState(33)
    cfg = ORBConfig(height=h, width=w, max_hamming=256, row_band=40)
    intr = CameraIntrinsics(fx=120.0, cx=w / 2.0, cy=h / 2.0,
                            baseline=0.2)
    img_l = jnp.asarray(rng.randint(0, 256, (h, w)).astype(np.float32))
    img_r = jnp.asarray(rng.randint(0, 256, (h, w)).astype(np.float32))
    fl = _random_features(rng, 27, h, w)
    fr = _random_features(rng, 19, h, w)
    # push some left windows against/over every border
    xy = np.asarray(fl.xy).copy()
    xy[:4] = [[0.0, 0.0], [w - 1.0, h - 1.0], [-5.3, h / 2.0],
              [w / 2.0, h + 4.9]]
    fl = fl._replace(xy=jnp.asarray(xy))
    matches = _system(cfg).stereo_match(fl, fr)
    want = sad_rectify_unfused(img_l, img_r, fl, fr, matches, cfg, intr,
                               impl="ref")
    for impl in ("ref", "pallas"):
        got = _system(cfg, intr, impl=impl).sad_rectify(
            img_l, img_r, fl, fr, matches)
        for f in want._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(want, f)),
                                          err_msg=f"{impl} {f}")


# ---------------------------------------------------------------------------
# Satellite: _gather_patches border-clamp audit vs the per-pixel oracle.

@pytest.mark.parametrize("ph,pw", [(11, 11), (11, 21), (5, 9)])
def test_gather_patches_pinned_to_bruteforce(ph, pw):
    """``matching._gather_patches`` (pad-then-slice) vs the python-loop
    per-pixel clamp oracle: keypoints within half a window of every
    edge, exactly on corners, fractional (round-half-even) and fully
    out of range."""
    h, w = 48, 37
    rng = np.random.RandomState(41)
    img = rng.randint(0, 256, (h, w)).astype(np.float32)
    xy = np.array([
        [0.0, 0.0], [w - 1.0, h - 1.0],                  # corners
        [ph // 2 - 1.0, pw // 2 - 1.0],                  # inside half-win
        [w - pw // 2 + 0.0, h - ph // 2 + 0.0],
        [0.5, 0.5], [1.5, 2.5],                          # half-even ties
        [w - 1.5, h - 1.5],
        [-7.9, 3.0], [w + 12.2, h + 0.4],                # out of range
        [w / 3.0, -0.5],
    ], np.float32)
    xy = np.concatenate([xy, np.stack([rng.uniform(-3, w + 3, 12),
                                       rng.uniform(-3, h + 3, 12)],
                                      1).astype(np.float32)])
    want = ref.gather_patches_bruteforce(img, xy, ph, pw)
    got = _gather_patches(jnp.asarray(img), jnp.asarray(xy), ph, pw)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_gather_patches_through_masked_right_index():
    """Right strips gathered through ``matches.right_index`` pointing at
    an invalid (masked) feature resolve to right feature 0 — the strip
    the oracle, the gather path and the fused kernel must all read."""
    h, w = 64, 96
    rng = np.random.RandomState(42)
    img = rng.randint(0, 256, (h, w)).astype(np.float32)
    fr = _random_features(rng, 9, h, w, valid_frac=0.0)
    right_index = jnp.zeros(5, jnp.int32)        # the where(valid, idx, 0)
    xy_r = np.asarray(fr.xy)[np.asarray(right_index)]
    want = ref.gather_patches_bruteforce(img, xy_r, 11, 21)
    got = _gather_patches(jnp.asarray(img), jnp.asarray(xy_r), 11, 21)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        want, np.tile(want[:1], (5, 1, 1)))      # all rows == feature 0's


# ---------------------------------------------------------------------------
# Satellite: temporal_match through the match-only mode, asymmetric radii.

@pytest.mark.parametrize("rx,ry", [(40.0, 8.0), (12.0, 60.0),
                                   (48.0, None)])
def test_temporal_match_asymmetric_radii_vs_bruteforce(rx, ry):
    """The rectangular window (+-rx in x via the meta shift, +-ry in y
    via the row band) equals the python-loop reference for asymmetric
    radii on both impls; ry=None keeps the legacy square window."""
    rng = np.random.RandomState(51)
    cfg = ORBConfig(height=480, width=640, max_hamming=256)
    fa = _random_features(rng, 41, 480, 640)
    fb = _random_features(rng, 33, 480, 640)
    # plant near-duplicates so the gates accept some matches
    desc_b = np.asarray(fb.desc).copy()
    desc_b[:12] = np.asarray(fa.desc)[:12]
    xy_b = np.asarray(fb.xy).copy()
    eff_ry = rx if ry is None else ry
    xy_b[:12] = (np.asarray(fa.xy)[:12]
                 + np.stack([rng.uniform(-rx, rx, 12),
                             rng.uniform(-eff_ry, eff_ry, 12)], 1))
    fb = fb._replace(desc=jnp.asarray(desc_b),
                     xy=jnp.asarray(xy_b.astype(np.float32)),
                     level=fb.level.at[:12].set(fa.level[:12]),
                     valid=fb.valid.at[:12].set(True))
    meta_a = np.stack([np.asarray(fa.xy)[:, 0] + rx,
                       np.asarray(fa.xy)[:, 1],
                       np.asarray(fa.level, np.float32),
                       np.asarray(fa.valid, np.float32)], 1)
    meta_b = np.stack([np.asarray(fb.xy)[:, 0], np.asarray(fb.xy)[:, 1],
                       np.asarray(fb.level, np.float32),
                       np.asarray(fb.valid, np.float32)], 1)
    want_d, want_i = ref.hamming_match_bruteforce(
        fa.desc, meta_a, fb.desc, meta_b, row_band=eff_ry,
        max_disparity=2.0 * rx)
    want_valid = ((want_i >= 0) & (want_d <= cfg.max_hamming)
                  & np.asarray(fa.valid))
    for impl in ("ref", "pallas"):
        tm = _system(cfg, impl=impl).temporal_match(fa, fb,
                                                    search_radius=rx,
                                                    search_radius_y=ry)
        np.testing.assert_array_equal(np.asarray(tm.distance), want_d,
                                      err_msg=impl)
        np.testing.assert_array_equal(np.asarray(tm.valid), want_valid,
                                      err_msg=impl)
        np.testing.assert_array_equal(
            np.asarray(tm.right_index), np.where(want_valid, want_i, 0),
            err_msg=impl)
    assert want_valid.any()


def test_temporal_match_single_launch():
    rng = np.random.RandomState(52)
    cfg = ORBConfig(height=96, width=144)
    fa = _random_features(rng, 30, 96, 144)
    fb = _random_features(rng, 30, 96, 144)
    vs = _system(cfg, impl="pallas")
    with ops.launch_audit() as audit:
        vs.temporal_match(fa, fb)    # first call: traces under the audit
    assert audit.count == 1


# ---------------------------------------------------------------------------
# Launch budget: the acceptance number of this refactor.

def test_quad_frame_three_launches():
    """Acceptance: a traced quad frame costs exactly 3 Pallas launches —
    2 FE (dense + sparse, all cameras x all levels) + 1 fused FM (both
    stereo pairs in one grid)."""
    cfg = ORBConfig(height=64, width=96, max_features=16, n_levels=2,
                    max_disparity=32)
    intr = CameraIntrinsics(cx=48.0, cy=32.0)
    rng = np.random.RandomState(53)
    imgs = jnp.asarray(rng.randint(0, 256, (4, 64, 96))
                       .astype(np.float32))
    vs = VisualSystem(RigConfig.quad(intr), PipelineConfig(orb=cfg))
    assert vs.traced_launches("process_frame", imgs) == 3
    # and the fused FM itself is exactly ONE of those launches
    assert vs.traced_launches("extract", imgs) == 2


# ---------------------------------------------------------------------------
# Hypothesis property suite (runs where hypothesis is installed — CI).

if HAVE_HYPOTHESIS:

    @given(n_pairs=st.integers(1, 3), k=st.integers(1, 40),
           m=st.integers(1, 40), seed=st.integers(0, 2**16))
    @settings(max_examples=15, deadline=None)
    def test_prop_fused_equals_unfused_ref(n_pairs, k, m, seed):
        """Full-FM property: for random pair counts and K/M (spanning
        non-multiples of every block size), the fused jnp path equals
        the unfused oracle bit-for-bit on every field."""
        h, w = 48, 71
        imgs_l, imgs_r, fls, frs = _pair_inputs(seed, n_pairs, k, m, h, w)
        cfg = ORBConfig(height=h, width=w, row_band=20, max_disparity=80,
                        max_hamming=160)
        intr = CameraIntrinsics(fx=90.0, cx=w / 2.0, cy=h / 2.0,
                                baseline=0.15)
        mf, df = _fused(imgs_l, imgs_r, _stack_feats(fls),
                                  _stack_feats(frs), cfg, intr,
                                  impl="ref")
        want = [_unfused(imgs_l[p], imgs_r[p], fls[p], frs[p],
                                   cfg, intr, impl="ref")
                for p in range(n_pairs)]
        _assert_pair_equal(mf, [wm for wm, _ in want],
                           f"P={n_pairs} K={k} M={m}")
        _assert_pair_equal(df, [wd for _, wd in want],
                           f"P={n_pairs} K={k} M={m}")

    @given(n_pairs=st.integers(1, 2), k=st.integers(1, 20),
           m=st.integers(1, 20), seed=st.integers(0, 2**16))
    @settings(max_examples=8, deadline=None)
    def test_prop_fused_pallas_equals_unfused(n_pairs, k, m, seed):
        """Pallas-interpret megakernel vs the unfused oracle for random
        K/M/pair counts (block padding, M-tile sweep boundaries)."""
        h, w = 40, 57
        imgs_l, imgs_r, fls, frs = _pair_inputs(seed, n_pairs, k, m, h, w)
        cfg = ORBConfig(height=h, width=w, row_band=15, max_disparity=60,
                        max_hamming=180)
        intr = CameraIntrinsics(fx=90.0, cx=w / 2.0, cy=h / 2.0,
                                baseline=0.15)
        mf, df = _fused(imgs_l, imgs_r, _stack_feats(fls),
                                  _stack_feats(frs), cfg, intr,
                                  impl="pallas")
        want = [_unfused(imgs_l[p], imgs_r[p], fls[p], frs[p],
                                   cfg, intr, impl="ref")
                for p in range(n_pairs)]
        _assert_pair_equal(mf, [wm for wm, _ in want],
                           f"P={n_pairs} K={k} M={m}")
        _assert_pair_equal(df, [wd for _, wd in want],
                           f"P={n_pairs} K={k} M={m}")

    @given(k=st.integers(1, 30), seed=st.integers(0, 2**16))
    @settings(max_examples=10, deadline=None)
    def test_prop_gather_patches_bruteforce(k, seed):
        """Clamp property: pad-then-slice gather == per-pixel clamp
        oracle for random window sizes and out-of-range centers."""
        rng = np.random.RandomState(seed)
        h, w = rng.randint(20, 60), rng.randint(20, 60)
        ph = 2 * rng.randint(1, 7) + 1
        pw = ph + 2 * rng.randint(0, 6)
        img = rng.randint(0, 256, (h, w)).astype(np.float32)
        xy = np.stack([rng.uniform(-8, w + 8, k),
                       rng.uniform(-8, h + 8, k)], 1).astype(np.float32)
        want = ref.gather_patches_bruteforce(img, xy, ph, pw)
        got = _gather_patches(jnp.asarray(img), jnp.asarray(xy), ph, pw)
        np.testing.assert_array_equal(np.asarray(got), want)
