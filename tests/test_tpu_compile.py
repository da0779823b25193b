"""Real-size Mosaic compiles of the four frame-path kernels, for a
described (not attached) TPU v5e.

Interpret mode on the CPU runs a kernel body without Mosaic's layout
rules, so it cannot see a block the chip's compiler refuses, a dynamic
slice off its tile alignment, a primitive Mosaic cannot lower, or more
VMEM than a kernel may use.  These tests compile each kernel the frame
path launches — the dense FE pyramid, the sparse describe pyramid, the
FM megakernel and the match-only kernel of the temporal match — at the
paper's 1280x720 quad rig with K = 1000 (``ORBConfig()``), in f32 and
uint8, with ``interpret=False``; and each frame-path stage through its
``ops`` wrapper at ORB-SLAM2's KITTI stereo shapes (one 1241x376 pair,
8 levels, K = 2,000) in uint8.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests
and only the one running this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ORBConfig
from repro.kernels import describe_fused, frontend_fused, matcher_fused, ops
from repro.kernels.ref import RADIUS

CFG = ORBConfig()
CAMERAS, PAIRS = 4, 2
DTYPES = {"f32": jnp.float32, "uint8": jnp.uint8}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _kernels(compiled):
    """The instruction names of the Mosaic kernels, without their
    ``.<n>`` suffix: the names a profiler trace shows, which the chip
    benchmark's ``kernels/<k>.py`` ``NAMES`` match."""
    text = compiled.as_text()
    return [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%([A-Za-z_][\w-]*?)(?:\.\d+)? = .*"
        r'custom_call_target="tpu_custom_call"', text, re.M)]


def _levels():
    return [CFG.level_shape(lvl) for lvl in range(CFG.n_levels)]


@pytest.mark.parametrize("precision", sorted(DTYPES))
def test_dense_fe_pyramid_compiles(one_chip, precision):
    tile = frontend_fused.TILE_H
    hc = max(h + (-h) % tile for h, _ in _levels())
    wc = max(w + (-w) % tile for _, w in _levels())
    n = CFG.n_levels * CAMERAS
    halo = 2 * frontend_fused.FUSED_HALO
    compiled = _compile(
        one_chip,
        lambda x, hw: frontend_fused.frontend_fused_pyramid_pallas(
            x, hw, threshold=float(CFG.fast_threshold)),
        ((n, hc + halo, wc + halo), DTYPES[precision]),
        ((n, 2), jnp.int32))
    assert _kernels(compiled) == ["frontend_fused_pyramid_pallas"]


@pytest.mark.parametrize("precision", sorted(DTYPES))
def test_sparse_describe_pyramid_compiles(one_chip, precision):
    kb = describe_fused.KP_BLOCK
    blocks = [-(-k // kb) for k in CFG.features_per_level()]
    offsets = tuple(sum(blocks[:i]) for i in range(len(blocks)))
    hc, wc = ops._describe_canvas(_levels())
    assert hc >= max(h for h, _ in _levels()) + 2 * RADIUS
    n = CFG.n_levels * CAMERAS
    compiled = _compile(
        one_chip,
        lambda r, s, xy, hw: describe_fused.describe_fused_pyramid_pallas(
            r, s, xy, hw, level_offsets=offsets),
        ((n, hc, wc), DTYPES[precision]), ((n, hc, wc), DTYPES[precision]),
        ((CAMERAS, sum(blocks) * kb, 2), jnp.int32),
        ((sum(blocks), 2), jnp.int32))
    assert _kernels(compiled) == ["describe_fused_pyramid_pallas"]


def _fm_slab(ry, rx):
    h, w = CFG.height, CFG.width
    return jax.eval_shape(lambda x: ops._pad_fm_slab(x, ry, rx),
                          jax.ShapeDtypeStruct((PAIRS, h, w),
                                               jnp.float32)).shape


@pytest.mark.parametrize("precision,slab_mib", [("f32", 7.91),
                                                ("uint8", 1.98)])
def test_fm_megakernel_compiles_with_resident_slabs(one_chip, precision,
                                                    slab_mib):
    """The FM keeps each pair's whole padded level-0 slab pair resident
    in VMEM — 7.91 MiB per pair in f32 at 720p.  The compile passing is
    the compiler's verdict that it fits the default scoped VMEM; its
    memory analysis must account the slabs exactly as the shapes do."""
    k, m = CFG.max_features, CFG.max_features + (-CFG.max_features) % 128
    ry, sad = CFG.sad_window // 2, CFG.sad_range
    left, right = _fm_slab(ry, ry), _fm_slab(ry, ry + sad)
    dt = DTYPES[precision]
    compiled = _compile(
        one_chip,
        lambda dl, ml, dr, mr, xy0, il, ir:
            matcher_fused.match_rectify_fused_pallas(
                dl, ml, dr, mr, xy0, il, ir, row_band=float(CFG.row_band),
                max_disparity=float(CFG.max_disparity),
                max_hamming=CFG.max_hamming, patch=CFG.sad_window,
                sad_range=sad, true_h=CFG.height, true_w=CFG.width),
        ((PAIRS, k, 8), jnp.uint32), ((PAIRS, k, 4), jnp.float32),
        ((PAIRS, 8, m), jnp.uint32), ((PAIRS, 4, m), jnp.float32),
        ((PAIRS, 1, 2), jnp.float32), (left, dt), (right, dt))
    slab_bytes = sum(jnp.dtype(dt).itemsize * s[1] * s[2]
                     for s in (left, right))
    assert round(slab_bytes / 2 ** 20, 2) == slab_mib
    mem = compiled.memory_analysis()
    # Arguments = the slabs of both pairs + descriptor/meta rows (< 1 MiB).
    extra = mem.argument_size_in_bytes - PAIRS * slab_bytes
    assert 0 <= extra < 2 ** 20, mem
    assert _kernels(compiled) == ["match_rectify_fused_pallas"]


@pytest.mark.parametrize("n_pairs", [PAIRS, 4 * PAIRS])
def test_match_only_kernel_compiles(one_chip, n_pairs):
    """The temporal match of a localized frame (2 pairs) and of a 4-rig
    fleet frame (8 pairs); descriptors carry no image dtype."""
    k = CFG.max_features + (-CFG.max_features) % matcher_fused.MO_BK
    compiled = _compile(
        one_chip,
        lambda dl, ml, dr, mr: matcher_fused.match_fused_pallas(
            dl, ml, dr, mr, row_band=48.0, max_disparity=48.0),
        ((n_pairs, k, 8), jnp.uint32), ((n_pairs, k, 4), jnp.float32),
        ((n_pairs, 8, k), jnp.uint32), ((n_pairs, 4, k), jnp.float32))
    assert _kernels(compiled) == ["match_fused_pallas"]


#: ORB-SLAM2's KITTI stereo settings: one 1241x376 pair, 8 levels at
#: 1.2, K = 2,000 (``benchmarks/chip/configs/kitti_stereo.json``).
KITTI = ORBConfig(height=376, width=1241, n_levels=8, max_features=2000,
                  max_disparity=718, max_hamming=75)


def _kitti_stage(stage):
    """(program, argument shapes, its kernel) of one uint8 frame-path
    stage at KITTI's shapes, through the ``ops`` wrappers that pad."""
    shapes = [KITTI.level_shape(lvl) for lvl in range(KITTI.n_levels)]
    ks = KITTI.features_per_level()
    k, n = sum(ks), len(shapes)
    slabs = [((2,) + s, jnp.uint8) for s in shapes]
    rows = [((1, k, 8), jnp.uint32), ((1, k, 4), jnp.float32)] * 2
    if stage == "dense_fe":
        return (lambda *lv: ops.fast_blur_nms_pyramid(
                    list(lv), float(KITTI.fast_threshold), impl="pallas"),
                slabs, "frontend_fused_pyramid_pallas")
    if stage == "describe":
        return (lambda *a: ops.orient_describe_pyramid(
                    list(a[:n]), list(a[n:2 * n]), list(a[2 * n:]),
                    impl="pallas"),
                slabs + slabs + [((2, kl, 2), jnp.int32) for kl in ks],
                "describe_fused_pyramid_pallas")
    if stage == "fm":
        return (lambda dl, ml, dr, mr, il, ir: ops.match_rectify_fused(
                    dl, ml, dr, mr, il, ir, row_band=float(KITTI.row_band),
                    max_disparity=float(KITTI.max_disparity),
                    max_hamming=KITTI.max_hamming, impl="pallas"),
                rows + [((1, KITTI.height, KITTI.width), jnp.uint8)] * 2,
                "match_rectify_fused_pallas")
    return (lambda dl, ml, dr, mr: ops.match_rectify_fused(
                dl, ml, dr, mr, row_band=48.0, max_disparity=96.0,
                impl="pallas"),
            rows, "match_fused_pallas")


@pytest.mark.parametrize("stage", ["dense_fe", "describe", "fm",
                                   "temporal_match"])
def test_kitti_stereo_stages_compile(one_chip, monkeypatch, stage):
    """The width of 1241 px, off the 128 lanes, 16 camera x level slabs
    on one canvas, 8 level blocks of keypoints and the FM at K = 2,000,
    uint8, through the wrappers' own padding (interpret mode, which the
    CPU backend picks, is turned off here)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    fn, shapes, kernel = _kitti_stage(stage)
    assert _kernels(_compile(one_chip, fn, *shapes)) == [kernel]
