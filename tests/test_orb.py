"""Behavioural tests of the ORB extraction stages (paper Sec. II-B)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ORBConfig, extract_features
from repro.core import brief, fast, pattern, pyramid
from repro.kernels import ref


def _corner_image(h=96, w=128, pts=((30, 40), (60, 90), (70, 20))):
    """Dark background with bright squares -> strong FAST corners."""
    img = np.full((h, w), 30.0, np.float32)
    for (y, x) in pts:
        img[y:y + 6, x:x + 6] = 220.0
    return jnp.asarray(img)


def test_pyramid_shapes_match_paper():
    cfg = ORBConfig(height=720, width=1280, n_levels=2)
    assert cfg.level_shape(0) == (720, 1280)
    assert cfg.level_shape(1) == (600, 1067)  # paper Sec. III-C


def test_pyramid_level_count_and_range():
    cfg = ORBConfig(height=96, width=128, n_levels=3)
    img = _corner_image()
    levels = pyramid.build_pyramid(img, cfg)
    assert len(levels) == 3
    for lvl, im in enumerate(levels):
        assert im.shape == cfg.level_shape(lvl)
        assert float(im.min()) >= 0.0 and float(im.max()) <= 255.0


def test_fast_detects_square_corners():
    img = _corner_image()
    cfg = ORBConfig(height=96, width=128, max_features=32, border=16)
    xy, score, theta, valid = fast.detect(img, cfg, k=32)
    got = {(int(x), int(y)) for (x, y), v in
           zip(np.asarray(xy), np.asarray(valid)) if v}
    # each stamped square produces corners near its own corners
    for (y0, x0) in ((30, 40), (60, 90)):
        near = [(x, y) for x, y in got
                if abs(x - x0) <= 8 and abs(y - y0) <= 8]
        assert near, f"no corner near square at {(x0, y0)}"


def test_nms_keeps_local_maxima_only():
    score = jnp.zeros((16, 16)).at[5, 5].set(10.0).at[5, 6].set(8.0)
    out = fast.nms3(score)
    assert float(out[5, 5]) == 10.0
    assert float(out[5, 6]) == 0.0


def test_topk_respects_border_and_static_shape():
    score = jnp.ones((64, 64))
    xy, vals, valid = fast.select_topk(score, k=16, border=16)
    assert xy.shape == (16, 2) and valid.shape == (16,)
    xs, ys = np.asarray(xy[:, 0]), np.asarray(xy[:, 1])
    v = np.asarray(valid)
    assert np.all(xs[v] >= 16) and np.all(xs[v] < 48)
    assert np.all(ys[v] >= 16) and np.all(ys[v] < 48)


def _topk_scores(case, dtype, h, w, border):
    """(scores (B, H, W), K) of one tie-order case; ``dtype`` picks how
    a case's values are drawn where the two dtypes differ."""
    is_float = np.dtype(dtype).kind == "f"
    if case.startswith("batch"):
        batch = int(case[len("batch"):])
        rng = np.random.RandomState(batch)
        return rng.randint(0, 4, (batch, h, w)), 150
    rng = np.random.RandomState(7)
    shape = (4, h, w)
    if case == "fewer_positive_than_k":
        scores = np.zeros(shape)
        scores.reshape(4, -1)[:, rng.choice(h * w, 40, replace=False)] = \
            rng.randint(1, 256, (4, 40))
        return scores, 150
    if case == "all_zero":
        return np.zeros(shape), 150
    if case == "k_every_inside_pixel":
        return rng.randint(0, 4, shape), (h - 2 * border) * (w - 2 * border)
    if case == "signed_zeros":
        # -0.0 beside +0.0 (the same 0 in int16), below and at the K-th.
        return rng.choice([-0.0, 0.0, 1.0], shape, p=[.4, .4, .2]), 1000
    if case == "full_depth":
        # non-integer floats over ~2^20 (the whole int16 range for
        # int16): the bisection runs to its full depth.
        if is_float:
            return rng.uniform(0, 2.0 ** 20, shape), 150
        return rng.randint(-2 ** 15, 2 ** 15, shape), 150
    if case == "only_0_and_255":
        return rng.choice([0, 255], shape), 150
    raise ValueError(case)


@pytest.mark.parametrize("case", [
    "batch1", "batch4", "batch16", "fewer_positive_than_k", "all_zero",
    "k_every_inside_pixel", "signed_zeros", "full_depth", "only_0_and_255"])
@pytest.mark.parametrize("dtype", [np.int16, np.float32])
def test_topk_tie_order_is_score_then_lowest_index(dtype, case):
    """FAST scores are small integers, so ties are the rule: the kept
    corners and their order must be (score descending, flat index
    ascending) for every camera whatever the batch size, the dtype or
    the range of the scores, with the lowest-index zeros filling K when
    fewer corners score above 0."""
    h, w, border = 40, 56, 3
    raw, k = _topk_scores(case, dtype, h, w, border)
    scores = raw.astype(dtype)
    xy, vals, valid = jax.vmap(
        lambda s: fast.select_topk(s, k, border))(jnp.asarray(scores))
    for b in range(scores.shape[0]):
        masked = np.zeros((h, w), dtype)
        masked[border:h - border, border:w - border] = \
            scores[b, border:h - border, border:w - border]
        flat = masked.reshape(-1)
        want = np.lexsort((np.arange(flat.size), -flat))[:k]
        np.testing.assert_array_equal(np.asarray(xy[b]),
                                      np.stack([want % w, want // w], -1))
        np.testing.assert_array_equal(np.asarray(vals[b]), flat[want])
        np.testing.assert_array_equal(np.asarray(valid[b]), flat[want] > 0)


def test_topk_lowers_without_a_full_map_sort():
    """The TPU lowers ``top_k`` and ``sort`` to full sorts: at 720p no
    such op may take a whole score map (4 x 921,600) as an operand —
    only the K survivors are sorted.  A lowering, nothing runs."""
    lowered = jax.jit(jax.vmap(lambda s: fast.select_topk(s, 545, 16))).lower(
        jax.ShapeDtypeStruct((4, 720, 1280), jnp.int16))
    sorts = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    inner = inner.operation
                    if "sort" in inner.name or "top_k" in inner.name:
                        sorts.append((inner.name,
                                      [str(v.type) for v in inner.operands]))
                    walk(inner)

    walk(lowered.compiler_ir("stablehlo").operation)
    assert sorts, "the K survivors' sort is missing"
    for name, operands in sorts:
        assert not any(str(720 * 1280) in t for t in operands), (name,
                                                                 operands)


def test_orientation_points_toward_bright_side():
    """Patch bright on +x side -> centroid to the right -> theta ~ 0;
    bright on +y side -> theta ~ +pi/2 (y down)."""
    img = np.full((64, 64), 10.0, np.float32)
    img[:, 40:] = 200.0  # bright right half
    theta = fast.orientations(jnp.asarray(img),
                              jnp.asarray([[32, 32]], np.int32))
    assert abs(float(theta[0])) < 0.2
    img2 = np.full((64, 64), 10.0, np.float32)
    img2[40:, :] = 200.0  # bright bottom half
    theta2 = fast.orientations(jnp.asarray(img2),
                               jnp.asarray([[32, 32]], np.int32))
    assert abs(float(theta2[0]) - np.pi / 2) < 0.2


def test_pattern_within_patch_after_rotation():
    """Paper Eq. 3: rotated pairs must stay inside the 31x31 patch."""
    for theta in np.linspace(0.0, 2 * np.pi, 17):
        rot = pattern.rotated_pattern(theta)
        assert np.abs(rot).max() <= pattern.PATCH_RADIUS


def test_descriptor_rotation_invariance():
    """The steered descriptor of a rotated image stays close in Hamming
    distance (rBRIEF's purpose, paper Sec. II-B2)."""
    rng = np.random.RandomState(3)
    img = rng.randint(0, 256, (96, 96)).astype(np.float32)
    img_r = np.rot90(img, k=1).copy()  # 90 deg CCW in array coords
    cfg = ORBConfig(height=96, width=96)
    sm = brief.smooth(jnp.asarray(img), cfg, impl="ref")
    sm_r = brief.smooth(jnp.asarray(img_r), cfg, impl="ref")
    c = 48
    # point (x, y) maps to (y, H-1-x) under np.rot90(k=1)
    x0, y0 = 60, 40
    x1, y1 = y0, 96 - 1 - x0
    th0 = fast.orientations(jnp.asarray(img), jnp.asarray([[x0, y0]],
                                                          np.int32))[0]
    th1 = fast.orientations(jnp.asarray(img_r), jnp.asarray([[x1, y1]],
                                                            np.int32))[0]
    d0 = brief.describe(sm, jnp.asarray([[x0, y0]], np.int32),
                        jnp.asarray([th0]))
    d1 = brief.describe(sm_r, jnp.asarray([[x1, y1]], np.int32),
                        jnp.asarray([th1]))
    dist = int(ref.hamming_distance_matrix(d0, d1)[0, 0])
    # unrotated-descriptor baseline distance would be ~128 (random);
    # steering must do much better.
    assert dist < 70, f"rotation invariance broken: hamming={dist}"


def test_extract_features_static_shapes_and_level_coords():
    img = _corner_image()
    cfg = ORBConfig(height=96, width=128, max_features=64, n_levels=2)
    fs = extract_features(img, cfg)
    assert fs.xy.shape == (64, 2)
    assert fs.desc.shape == (64, 8) and fs.desc.dtype == jnp.uint32
    # level-1 coordinates are scaled back to level-0 pixel space
    lvl = np.asarray(fs.level)
    xy = np.asarray(fs.xy)
    v = np.asarray(fs.valid)
    assert np.all(xy[v][:, 0] < 128.0 * 1.01)
    assert int(fs.count()) > 0
    if np.any(v & (lvl == 1)):
        # scaled coords may be fractional
        assert np.any(np.abs(xy[v & (lvl == 1)] % 1.0) > 0)


@pytest.mark.parametrize("k", [16, 33, 100])
def test_feature_budget_split(k):
    cfg = ORBConfig(height=720, width=1280, max_features=k, n_levels=2)
    ks = cfg.features_per_level()
    assert sum(ks) == k and all(x >= 1 for x in ks)
    assert ks[0] > ks[1]  # level 0 has more area -> larger budget
