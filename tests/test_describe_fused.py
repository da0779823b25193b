"""Fused sparse descriptor stage (orientation + rBRIEF) vs. oracles.

Three implementations must agree BIT-exactly on theta, the circular-
patch moments and the packed descriptors:

  * the Pallas kernel (interpret mode on CPU),
  * the jnp fallback (``ops.orient_describe_batched(..., impl="ref")``),
  * the per-image ref oracle (``ref.orient_describe``).

The kernel resolves taps with a selection matmul whose SIGN equals the
oracle's gather-compare exactly, so equality is exact, not approximate:
one bf16 MXU pass on integer (uint8) slabs, f32 ``Precision.HIGHEST``
on float slabs.  Both slab dtypes are held to the oracle.
Descriptor differences against the pre-refactor EXACT steering
(``ref.describe_steered``) are bounded by the documented 30-degree
angle-bin quantization and pinned here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis
from repro.analysis import jaxpr_walk
from repro.core import (ORBConfig, extract_features,
                        extract_features_batched)
from repro.core import brief, fast
from repro.kernels import describe_fused, ops, pattern, ref

SLAB_DTYPES = {"float32": np.float32, "uint8": np.uint8}


def _imgs(rng, b, h, w, dtype=np.float32):
    return jnp.asarray(rng.randint(0, 256, (b, h, w)).astype(dtype))


def _keypoints(rng, b, k, h, w, border=16):
    return jnp.asarray(np.stack([
        rng.randint(border, w - border, (b, k)),
        rng.randint(border, h - border, (b, k))], axis=-1).astype(np.int32))


def _assert_tri_impl_exact(raw, smoothed, xy):
    """pallas == jnp fallback == per-image oracle, bit for bit."""
    out_pl = ops.orient_describe_batched(raw, smoothed, xy, impl="pallas")
    out_ref = ops.orient_describe_batched(raw, smoothed, xy, impl="ref")
    for a, b, name in zip(out_pl, out_ref, ("theta", "moments", "desc")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"pallas vs fallback {name}")
    for c in range(raw.shape[0]):
        th, mom, desc = ref.orient_describe(raw[c], smoothed[c], xy[c])
        np.testing.assert_array_equal(np.asarray(out_pl[0][c]),
                                      np.asarray(th),
                                      err_msg=f"camera {c} theta")
        np.testing.assert_array_equal(np.asarray(out_pl[1][c]),
                                      np.asarray(mom),
                                      err_msg=f"camera {c} moments")
        np.testing.assert_array_equal(np.asarray(out_pl[2][c]),
                                      np.asarray(desc),
                                      err_msg=f"camera {c} desc")
    return out_pl


@pytest.mark.parametrize("slab_dtype", sorted(SLAB_DTYPES))
@pytest.mark.parametrize("shape,b,k", [
    ((70, 111), 3, 21),      # non-square, K not a KP_BLOCK multiple
    ((96, 128), 4, 8),
    ((37, 53), 2, 5),        # image smaller than one dense tile
])
def test_tri_impl_bitexact(rng, shape, b, k, slab_dtype):
    h, w = shape
    raw = _imgs(rng, b, h, w, SLAB_DTYPES[slab_dtype])
    smoothed = ops.fast_blur_nms_batched(raw, 20.0, impl="ref")[0]
    assert smoothed.dtype == raw.dtype
    xy = _keypoints(rng, b, k, h, w)
    out = _assert_tri_impl_exact(raw, smoothed, xy)
    assert out[0].shape == (b, k)
    assert out[1].shape == (b, k, 2)
    assert out[2].shape == (b, k, 8) and out[2].dtype == jnp.uint32


def test_float_slabs_with_fractional_values(rng):
    """A smoothed float slab off the integers (an unquantized blur),
    which one bf16 pass would round: its f32 HIGHEST taps keep the
    descriptors exact."""
    raw = _imgs(rng, 2, 64, 80)
    smoothed = ops.fast_blur_nms_batched(raw, 20.0, quantized=False,
                                         impl="ref")[0]
    assert not np.array_equal(np.asarray(smoothed),
                              np.round(np.asarray(smoothed)))
    _assert_tri_impl_exact(raw, smoothed, _keypoints(rng, 2, 16, 64, 80))


def test_uint8_saturated_patches_at_bin_edges(rng):
    """The single-pass bf16 taps at their extremes: 0/255-only raw and
    smoothed slabs (the largest pixel, and ties everywhere), and
    keypoints whose angle lies within BIN_MARGIN of a steering-bin edge,
    so the kernel steers both candidate bins (b_lo != b_hi) and the
    caller keeps the oracle's."""
    b, h, w = 2, 72, 104
    raw = jnp.asarray(255 * rng.randint(0, 2, (b, h, w)).astype(np.uint8))
    blocks = rng.randint(0, 2, (b, h // 4 + 1, w // 4 + 1)).astype(np.uint8)
    smoothed = jnp.asarray(
        255 * np.repeat(np.repeat(blocks, 4, 1), 4, 2)[:, :h, :w])
    cand = _keypoints(rng, b, 400, h, w)
    moments = jax.vmap(lambda im, p: ref.patch_theta(ref.extract_patches(
        im, p))[1])
    mom = moments(raw, cand)
    b_lo, b_hi = describe_fused.candidate_bins(mom[..., 0], mom[..., 1])
    edge = np.asarray(b_lo != b_hi)
    # 12 edge keypoints and 12 others a camera, in mixed order
    picks = [np.concatenate([np.flatnonzero(e)[:12],
                             np.flatnonzero(~e)[:12]]) for e in edge]
    assert all(len(p) == 24 for p in picks), edge.sum(axis=1)
    order = rng.permutation(24)
    xy = jnp.stack([cand[c, p[order]] for c, p in enumerate(picks)])
    theta, _, _ = _assert_tri_impl_exact(raw, smoothed, xy)
    # the oracle's bin falls on each side of the edges
    mom = moments(raw, xy)
    _, b_hi = describe_fused.candidate_bins(mom[..., 0], mom[..., 1])
    on_hi = np.asarray(ref.theta_to_bin(theta) == b_hi)
    assert 0 < on_hi.sum() < on_hi.size


def _dots(jaxpr):
    """Every ``dot_general`` equation of ``jaxpr``, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for val in eqn.params.values():
            for sub in jaxpr_walk._sub_jaxprs(val):
                out += _dots(sub)
    return out


@pytest.mark.parametrize("entry,taps", [
    ("frame_u8", (jnp.bfloat16, None)),
    ("frame_f32", (jnp.float32, jax.lax.Precision.HIGHEST)),
])
def test_describe_precision_follows_slab_dtype(entry, taps):
    """The describe ``pallas_call`` of a session's frame, read from its
    jaxpr (no chip, no execution): on the uint8 datapath every dot has
    bf16 operands at default precision; on f32 slabs the tap dots stay
    f32 HIGHEST and only the bit pack is bf16."""
    te = analysis.trace_entry(next(s for s in analysis.MATRIX
                                   if s.name == entry),
                              height=96, width=128, max_features=64)
    (site,) = [s for s in te.sites
               if s.eqn.params.get("name") == "describe_fused_pyramid_pallas"]
    dots = _dots(site.kernel_jaxpr)
    packs = [d for d in dots if d.invars[1].aval.shape == (256, 16)]
    tap_dots = [d for d in dots if d.invars[1].aval.shape == (31, 256)]
    assert len(packs) == 2 and len(tap_dots) == 4
    for eqn, (dtype, precision) in (
            [(d, (jnp.bfloat16, None)) for d in packs]
            + [(d, taps) for d in tap_dots]):
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype]
        assert eqn.params["preferred_element_type"] == jnp.float32
        got = eqn.params["precision"]
        assert (got if got is None else set(got)) == (
            None if precision is None else {precision}), got


def test_paper_level1_shape(rng):
    """600x1067 — the paper's 1280x720 level-1 shape (Sec. III-C), far
    from tile alignment on both axes."""
    raw = _imgs(rng, 1, 600, 1067)
    smoothed = ops.fast_blur_nms_batched(raw, 20.0, impl="ref")[0]
    xy = _keypoints(rng, 1, 16, 600, 1067)
    _assert_tri_impl_exact(raw, smoothed, xy)


def test_border_adjacent_and_out_of_range_keypoints(rng):
    """Keypoints on the image border use edge-padded patches; coords
    outside the image (top-K padding rows carry arbitrary values) are
    clamped identically by kernel and oracle."""
    h, w = 64, 96
    raw = _imgs(rng, 2, h, w)
    smoothed = ops.fast_blur_nms_batched(raw, 20.0, impl="ref")[0]
    pts = np.array([
        [0, 0], [w - 1, h - 1], [0, h - 1], [w - 1, 0],
        [15, 15], [16, 16], [w - 16, h - 16],
        [-5, 10], [w + 40, h + 40], [10, -3],     # out of range -> clamped
    ], dtype=np.int32)
    xy = jnp.asarray(np.broadcast_to(pts, (2, *pts.shape)).copy())
    out = _assert_tri_impl_exact(raw, smoothed, xy)
    assert np.isfinite(np.asarray(out[0])).all()
    assert np.isfinite(np.asarray(out[1])).all()


def test_all_invalid_level(rng):
    """A level with NO corners (blank image): top-K emits valid=False
    rows with degenerate coords; the sparse stage must stay finite and
    agree across impls, and the extractor must mask everything."""
    imgs = jnp.zeros((2, 64, 96), jnp.float32)
    cfg = ORBConfig(height=64, width=96, max_features=16, n_levels=2)
    for impl in ("ref", "pallas"):
        feats = extract_features_batched(imgs, cfg, impl=impl)
        assert int(feats.count()) == 0
        assert np.isfinite(np.asarray(feats.theta)).all()
    f_ref = extract_features_batched(imgs, cfg, impl="ref")
    f_pl = extract_features_batched(imgs, cfg, impl="pallas")
    for f in f_ref._fields:
        np.testing.assert_array_equal(np.asarray(getattr(f_ref, f)),
                                      np.asarray(getattr(f_pl, f)),
                                      err_msg=f)


def test_orientation_only_variant_matches_full(rng):
    """smoothed=None selects the orientation-only kernel; its theta and
    moments must equal the full kernel's."""
    raw = _imgs(rng, 2, 70, 90)
    smoothed = ops.fast_blur_nms_batched(raw, 20.0, impl="ref")[0]
    xy = _keypoints(rng, 2, 12, 70, 90)
    for impl in ("ref", "pallas"):
        th_o, mom_o, desc_o = ops.orient_describe_batched(
            raw, None, xy, impl=impl)
        th_f, mom_f, _ = ops.orient_describe_batched(
            raw, smoothed, xy, impl=impl)
        assert desc_o is None
        np.testing.assert_array_equal(np.asarray(th_o), np.asarray(th_f))
        np.testing.assert_array_equal(np.asarray(mom_o), np.asarray(mom_f))


def test_extractor_two_launches_per_frame(rng):
    """Acceptance: extract_features_batched issues exactly 2 launches
    per FRAME (1 dense fused + 1 sparse descriptor) for ALL cameras x
    ALL pyramid levels, via the traced launch counter; the per-level
    reference schedule still costs 2 per level."""
    from repro.core import extract_features_per_level
    imgs = _imgs(rng, 4, 96, 128)
    cfg = ORBConfig(height=96, width=128, max_features=48, n_levels=2)
    with ops.launch_audit() as audit:
        jax.eval_shape(
            lambda im: extract_features_batched(im, cfg, impl="pallas"),
            imgs)
    assert audit.count == 2
    with ops.launch_audit() as audit:
        jax.eval_shape(
            lambda im: extract_features_per_level(im, cfg, impl="pallas"),
            imgs)
    assert audit.count == 2 * cfg.n_levels


def test_detect_theta_pinned_to_batched_path(rng):
    """Satellite fix: fast.detect routes orientation through the same
    dispatch as the batched extractor, so single-image and batched theta
    are bit-identical — across BOTH impls."""
    img = _imgs(rng, 1, 96, 128)[0]
    cfg = ORBConfig(height=96, width=128, max_features=32, n_levels=1)
    k = cfg.features_per_level()[0]
    xy_d, _, theta_d, valid_d = fast.detect(img, cfg, k, impl="pallas")
    feats = extract_features(img, cfg, impl="pallas")
    np.testing.assert_array_equal(np.asarray(xy_d, np.float32),
                                  np.asarray(feats.xy))
    np.testing.assert_array_equal(np.asarray(theta_d),
                                  np.asarray(feats.theta))
    # and ref == pallas on the single-image path itself
    _, _, theta_ref, _ = fast.detect(img, cfg, k, impl="ref")
    np.testing.assert_array_equal(np.asarray(theta_d),
                                  np.asarray(theta_ref))


def test_lut_binning_quantization_pinned(rng):
    """The ONLY descriptor change vs the pre-refactor exact steering is
    the 30-degree angle-bin quantization.  Pin its size: mean Hamming
    distance well under the random-descriptor baseline (~128), and
    near-zero when theta sits on a bin center."""
    dists = []
    for seed in range(3):
        r = np.random.RandomState(seed)
        img = jnp.asarray(r.randint(0, 256, (128, 160)).astype(np.float32))
        cfg = ORBConfig(height=128, width=160)
        sm = brief.smooth(img, cfg, impl="ref")
        xy = jnp.asarray(np.stack([r.randint(16, 144, 64),
                                   r.randint(16, 112, 64)], 1).astype(np.int32))
        theta = fast.orientations(img, xy, impl="ref")
        d_lut = brief.describe(sm, xy, theta)
        d_exact = ref.describe_steered(sm, xy, theta)
        dists.append(np.asarray(
            ref.hamming_distance_matrix(d_lut, d_exact)).diagonal())
    d = np.concatenate(dists)
    # observed: mean ~43, max 98 of 256 (bins are 30 deg -> taps move by
    # up to |r| * 15 deg ~ 3.4 px).  Random descriptors would give ~128.
    assert d.mean() < 56.0, f"quantization too large: mean {d.mean()}"
    assert d.max() <= 128, f"quantization too large: max {d.max()}"

    # At bin centers the LUT row IS the rotated pattern; residual bits
    # come only from f32 (exact path) vs f64 (LUT) trig rounding at
    # half-integer taps.  Observed <= 4 bits.
    img = jnp.asarray(np.random.RandomState(9).randint(
        0, 256, (128, 160)).astype(np.float32))
    sm = brief.smooth(img, ORBConfig(height=128, width=160), impl="ref")
    r = np.random.RandomState(10)
    xy = jnp.asarray(np.stack([r.randint(16, 144, pattern.N_ANGLE_BINS),
                               r.randint(16, 112, pattern.N_ANGLE_BINS)],
                              1).astype(np.int32))
    centers = (np.arange(pattern.N_ANGLE_BINS) * pattern.ANGLE_BIN_STEP
               + np.pi) % (2 * np.pi) - np.pi
    th = jnp.asarray(centers, dtype=jnp.float32)
    dist = np.asarray(ref.hamming_distance_matrix(
        brief.describe(sm, xy, th),
        ref.describe_steered(sm, xy, th))).diagonal()
    assert dist.max() <= 8, f"bin-center mismatch: {dist}"


def test_steer_lut_geometry():
    """Every LUT tap stays inside the 31x31 patch, and row b equals the
    exact rotation at the bin-b center angle (the LUT's definition)."""
    lut = pattern.STEER_LUT
    assert lut.shape == (pattern.N_ANGLE_BINS, pattern.N_PAIRS, 2)
    assert lut.min() >= 0 and lut.max() < 31 * 31
    for b in range(pattern.N_ANGLE_BINS):
        rot = pattern.rotated_pattern(b * pattern.ANGLE_BIN_STEP)
        a_lin = (rot[:, 1] + 15) * 31 + (rot[:, 0] + 15)
        b_lin = (rot[:, 3] + 15) * 31 + (rot[:, 2] + 15)
        np.testing.assert_array_equal(lut[b, :, 0], a_lin)
        np.testing.assert_array_equal(lut[b, :, 1], b_lin)
