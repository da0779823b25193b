"""The KITTI stereo deployment (``benchmarks/chip/configs/kitti_stereo.json``:
ORB-SLAM2's KITTI settings, one 1241x376 pair, 8 levels at 1.2, 2,000
features) cut for the CPU: 176x583, 8 levels, 256 features, intrinsics
scaled by 176/376.  Its smallest level is 49x163, so every level keeps
an interior past the 16-px border.  Over three localized frames of the
benchmark's stream scene the Pallas path (interpret mode) equals the
jnp path leaf for leaf, and both equal the benchmark's plain reference.
"""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import check, compare, program, reference, traffic
from repro.core import ORBConfig, pyramid

ROOT = Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
CONFIG = json.loads((CHIP / "configs" / "kitti_stereo.json").read_text())
SCENE = json.loads((CHIP / "traffic" / "stream.json").read_text())["scene"]
SEED = 3_000_000_123


def _cut(height, width, max_features=256, impl="ref"):
    cfg = copy.deepcopy(CONFIG)
    s = height / CONFIG["orb"]["height"]
    cfg["orb"].update(height=height, width=width, max_features=max_features)
    cam = cfg["camera"]
    cam.update(fx=cam["fx"] * s, fy=cam["fy"] * s, cx=cam["cx"] * s,
               cy=cam["cy"] * s)
    cfg["impl"] = impl
    return cfg


CUT = _cut(176, 583)


@pytest.fixture(scope="module")
def frames():
    return traffic.render(CUT, SCENE, 3, SEED).images


@pytest.fixture(scope="module")
def answers(frames):
    out = {}
    for impl in ("ref", "pallas"):
        vs = program.session(dict(CUT, impl=impl))
        out[impl] = [compare.to_tree(jax.device_get(vs.process_frame(f)))
                     for f in frames]
    return out


def test_cut_keeps_the_published_shape():
    shapes = pyramid.level_shapes(ORBConfig(**CUT["orb"]))
    assert len(shapes) == 8 and shapes[-1] == (49, 163)
    full = pyramid.level_shapes(ORBConfig(**CONFIG["orb"]))
    assert full[0] == (376, 1241) and full[-1] == (106, 346)
    assert ORBConfig(**CONFIG["orb"]).features_per_level() == [
        650, 447, 311, 216, 150, 104, 72, 50]
    assert CONFIG["rig"]["pairs"] == [[0, 1]]


def test_pallas_equals_ref_leaf_for_leaf(answers):
    for got, want in zip(answers["pallas"], answers["ref"]):
        flat_g, tree_g = jax.tree.flatten(got)
        flat_w, tree_w = jax.tree.flatten(want)
        assert tree_g == tree_w
        for g, w in zip(flat_g, flat_w):
            np.testing.assert_array_equal(g, w)


def test_program_equals_the_plain_reference(answers, frames):
    want = check.reference_answers(CUT, frames, chain=True)
    for i, got in enumerate(answers["pallas"]):
        assert compare.frame_mismatches(got, want[i],
                                        compare.LOCALIZED) == {}, i
        stereo = got["stereo"]
        assert stereo["matches"]["valid"].sum() > 100
    assert all(bool(a["pose"]["valid"]) for a in answers["pallas"][1:])


def _exact_departures(levels, cfg):
    """Per level below the first: the pixels that differ from the
    float64 bilinear resize of the level above, rounded half to even,
    and how far each exact value lies from a half."""
    out = []
    with jax.enable_x64(True):
        for lvl in range(1, len(levels)):
            above = np.asarray(levels[lvl - 1], np.float64)
            exact = np.asarray(jax.image.resize(
                jnp.asarray(above), above.shape[:1] + cfg.level_shape(lvl),
                method="bilinear"))
            bad = np.asarray(levels[lvl]) != np.round(np.clip(exact, 0, 255))
            out.append(np.abs(exact[bad] % 1.0 - 0.5))
    return out


def test_pyramids_depart_from_the_exact_resize_only_at_half_ties():
    """At 144x477 the two smallest of 8 levels are 48 and 40 rows high.
    There the program's camera-batched resize and the reference's
    per-image one round a few pixels differently: each evaluates the
    same bilinear resize in float32, and where the exact value is a
    half (or within float32's error of one) the order of the sums
    decides the side.  Everywhere else both equal the exact value
    rounded half to even."""
    cfg = _cut(144, 477)
    ocfg = ORBConfig(**cfg["orb"])
    images = traffic.render(cfg, SCENE, 1, SEED).images[0]
    prog = jax.jit(lambda im: pyramid.build_pyramid_batched(
        im, ocfg, precision="uint8"))(images)
    ref = jax.jit(lambda im: [jnp.stack(lv) for lv in zip(
        *[reference.pyramid(im[c], cfg["orb"]) for c in range(2)])])(images)
    for levels in (prog, ref):
        for dist in _exact_departures(levels, ocfg):
            assert np.all(dist < 2e-3), dist.max()
