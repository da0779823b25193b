"""Jit'd dispatch wrappers around the Pallas kernels and jnp oracles.

Every op takes ``impl``:
  - "ref"     — pure-jnp oracle (ref.py), any backend.
  - "pallas"  — Pallas kernel: compiled by Mosaic on the TPU; on the
                CPU (tests) it runs in interpret mode; any other
                backend raises.
  - None      — the innermost ``use_impl`` context, else the process
                default (``set_default_impl`` / REPRO_KERNEL_IMPL env
                var), else "ref" on CPU and "pallas" on TPU.  Sessions
                (``core.pipeline.VisualSystem``) resolve their impl
                once from ``PipelineConfig`` and thread it explicitly.

Impl scoping and the launch audit are both context-var based so
parallel sessions (threads, concurrent test workers) never cross-talk:
``use_impl`` scopes the default impl, and ``launch_audit()`` yields a
counter that observes every Pallas launch traced inside its scope.
``set_default_impl`` / ``reset_launch_count`` / ``launch_count`` are
kept as legacy shims over the same machinery.

The wrappers own all padding/unpadding so kernels see tile-aligned
shapes and callers see exact shapes.
"""

from __future__ import annotations

import contextlib
import contextvars
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.describe_fused import (KP_BLOCK, WIN_H, WIN_W,
                                          _cast_slab,
                                          describe_fused_pyramid_pallas,
                                          single_pass_taps)
from repro.kernels.fast_detect import (HALO, TILE_H, TILE_W,
                                       fast_score_map_pallas)
from repro.kernels.frontend_fused import (FUSED_HALO, fast_score_from_taps,
                                          frontend_fused_pallas,
                                          frontend_fused_pyramid_pallas)
from repro.kernels.gaussian_blur import gaussian_blur7_pallas
from repro.kernels.hamming_match import BIG, BK, hamming_match_pallas
from repro.kernels.matcher_fused import (FM_BK, FM_BM, MO_BK, SAD_WIN_H,
                                         SAD_WIN_W, match_fused_pallas,
                                         match_rectify_fused_pallas,
                                         sad_fused_pallas)
from repro.kernels.sad_rectify import sad_search_pallas

_DEFAULT_IMPL: str | None = os.environ.get("REPRO_KERNEL_IMPL") or None

# Context-scoped impl override: ``use_impl`` installs a value here; the
# context var is per-thread (new threads start from defaults), so scoped
# overrides in one session/thread never leak into another.
_IMPL_VAR: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "repro_kernel_impl", default=None)


def _check_impl(impl: str | None) -> None:
    if impl not in (None, "ref", "pallas"):
        raise ValueError(
            f"unknown kernel impl {impl!r} (expected 'ref' or 'pallas'; "
            "check REPRO_KERNEL_IMPL)")


@contextlib.contextmanager
def use_impl(impl: str | None):
    """Scope the default kernel impl for the dynamic extent of the
    ``with`` block (context-var based: thread-safe, re-entrant)."""
    _check_impl(impl)
    token = _IMPL_VAR.set(impl)
    try:
        yield
    finally:
        _IMPL_VAR.reset(token)


def set_default_impl(impl: str | None) -> None:
    """Legacy shim: set the PROCESS-WIDE default impl.  Prefer scoping
    with ``use_impl`` or resolving once in a ``VisualSystem`` session —
    this global is shared across threads."""
    global _DEFAULT_IMPL
    _check_impl(impl)
    _DEFAULT_IMPL = impl


def resolve_impl(impl: str | None) -> str:
    if impl is None:
        impl = _IMPL_VAR.get()
    if impl is None:
        impl = _DEFAULT_IMPL
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    _check_impl(impl)
    return impl


# Trace-time Pallas launch audit: each pallas-path dispatch below bumps
# every active audit once per kernel launch appearing in the traced
# graph.  Benchmarks and tests open a ``launch_audit()`` scope around a
# trace (jax.eval_shape / jit tracing) to report how many kernel
# launches a schedule issues — the regression-trackable "fused vs seed"
# number when wall-clock is noisy.  Audits are context-var based so
# parallel sessions (threads) count independently; the legacy
# ``reset_launch_count`` / ``launch_count`` pair is a shim over a
# per-context counter.
class LaunchAudit:
    """Counter bound to one ``launch_audit()`` scope."""

    __slots__ = ("count",)

    def __init__(self) -> None:
        self.count = 0


_AUDIT_STACK: contextvars.ContextVar[tuple[LaunchAudit, ...]] = \
    contextvars.ContextVar("repro_launch_audits", default=())
_LEGACY_AUDIT: contextvars.ContextVar[LaunchAudit | None] = \
    contextvars.ContextVar("repro_launch_legacy", default=None)


@contextlib.contextmanager
def launch_audit():
    """Yield a ``LaunchAudit`` whose ``.count`` observes every Pallas
    launch traced inside the ``with`` block.  Scopes nest (an inner
    audit also feeds enclosing ones) and are thread-isolated."""
    audit = LaunchAudit()
    token = _AUDIT_STACK.set(_AUDIT_STACK.get() + (audit,))
    try:
        yield audit
    finally:
        _AUDIT_STACK.reset(token)


def _legacy_audit() -> LaunchAudit:
    audit = _LEGACY_AUDIT.get()
    if audit is None:
        audit = LaunchAudit()
        _LEGACY_AUDIT.set(audit)
    return audit


def reset_launch_count() -> None:
    """Legacy shim over the per-context counter; prefer
    ``launch_audit()``."""
    _legacy_audit().count = 0


def launch_count() -> int:
    """Legacy shim over the per-context counter; prefer
    ``launch_audit()``."""
    return _legacy_audit().count


def _count_launches(n: int = 1) -> None:
    _legacy_audit().count += n
    for audit in _AUDIT_STACK.get():
        audit.count += n


def _interpret() -> bool:
    """Mosaic on the TPU, interpret mode on the CPU (the test backend).
    Any other backend has no Pallas path here and raises rather than
    silently interpreting."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"impl='pallas' runs on 'tpu' (Mosaic) or 'cpu' (interpret "
            f"mode, for tests); the default backend is {backend!r}")
    return backend == "cpu"


def _pad_tiles(img: jnp.ndarray, halo: int, th: int, tw: int):
    """Edge-pad by halo and zero-pad H/W up to tile multiples.

    Returns (padded, (H, W)) where padded is ((H'+2h), (W'+2h))."""
    h, w = img.shape
    hp = (-h) % th
    wp = (-w) % tw
    padded = jnp.pad(img.astype(jnp.float32),
                     ((halo, halo + hp), (halo, halo + wp)), mode="edge")
    return padded, (h, w)


def _round_up(n: int, mult: int) -> int:
    return n + (-n) % mult


def dense_fe_canvas(shapes) -> tuple[int, int]:
    """(Hc, Wc) of the whole-pyramid dense kernel's common canvas: the
    largest level rounded up to whole TILE_H x TILE_W tiles.  Every
    camera x level slab is padded to it."""
    return (max(_round_up(h, TILE_H) for h, _ in shapes),
            max(_round_up(w, TILE_W) for _, w in shapes))


def fm_rows(k: int, m: int, match_only: bool = False) -> tuple[int, int]:
    """(K, M) rows of a pair in the FM grid: the K left features padded
    to the left block, the M right features to FM_BM."""
    return _round_up(k, MO_BK if match_only else FM_BK), _round_up(m, FM_BM)


def frame_grids(level_shapes, n_cameras: int, n_pairs: int,
                k: int) -> dict:
    """What one rig frame's dense FE and stereo FM kernels walk, from
    static shapes alone and by the functions their wrappers pad with:
    ``fe_tiles``, the dense grid's tiles over every camera x level slab;
    ``fe_tiles_real``, those that hold level pixels (the rest is canvas
    padding); ``fm_pairs``, the left x right candidate slots of the FM
    grid over every pair (K features a side)."""
    hc, wc = dense_fe_canvas(level_shapes)
    real = sum(_round_up(h, TILE_H) // TILE_H * (_round_up(w, TILE_W)
                                                 // TILE_W)
               for h, w in level_shapes)
    kp, mp = fm_rows(k, k)
    return {"fe_tiles": (n_cameras * len(level_shapes)
                         * (hc // TILE_H) * (wc // TILE_W)),
            "fe_tiles_real": n_cameras * real,
            "fm_pairs": n_pairs * kp * mp}


def describe_grid(level_ks, n_cameras: int, slab_dtype) -> dict:
    """What one rig frame's sparse describe kernel resolves in one bf16
    MXU pass, from static shapes: ``describe_bf16_slots``, the keypoint
    slots of its grid (every camera's level K padded to KP_BLOCK, as
    ``orient_describe_pyramid`` pads) on integer slabs; 0 on float
    slabs, whose taps keep f32 ``Precision.HIGHEST``."""
    slots = n_cameras * sum(_round_up(k, KP_BLOCK) for k in level_ks)
    return {"describe_bf16_slots":
            slots if single_pass_taps(slab_dtype) else 0}


def fast_score_map(img: jnp.ndarray, threshold: float,
                   impl: str | None = None) -> jnp.ndarray:
    """(H, W) image -> (H, W) float32 FAST-9/16 corner score map."""
    if resolve_impl(impl) == "ref":
        return _ref.fast_score_map(img, threshold)
    padded, (h, w) = _pad_tiles(img, HALO, TILE_H, TILE_W)
    _count_launches()
    out = fast_score_map_pallas(padded, threshold=float(threshold),
                                interpret=_interpret())
    return out[:h, :w]


def gaussian_blur7(img: jnp.ndarray, quantized: bool = True,
                   impl: str | None = None) -> jnp.ndarray:
    """(H, W) image -> (H, W) float32 7x7-Gaussian-smoothed image."""
    if resolve_impl(impl) == "ref":
        return _ref.gaussian_blur7(img, quantized=quantized)
    padded, (h, w) = _pad_tiles(img, HALO, TILE_H, TILE_W)
    _count_launches()
    out = gaussian_blur7_pallas(padded, quantized=quantized,
                                interpret=_interpret())
    return out[:h, :w]


def _blur_rawscore_jnp(x: jnp.ndarray, threshold: float, quantized: bool):
    """Shared jnp stencil body of the fused fallbacks: (B, H, W) float32
    OR uint8 -> (blur, raw score), each (B, H, W).  ONE shared edge-pad
    feeds both stencils, the FAST arc extrema use the van Herk block
    prefix/suffix scheme instead of materializing (16, H, W) stacks
    (min/max reassociation is exact, so results are unchanged), and the
    blur keeps the oracle's tap-summation order (float-exact).  uint8
    input runs the integer datapath (int32 accumulators, uint8 blur +
    int16 score out) — equal in value on quantized images (see
    ``ref.gaussian_blur7_u8`` / ``ref.fast_score_map_int``)."""
    _, h, w = x.shape
    integer = jnp.issubdtype(x.dtype, jnp.integer)
    if integer:
        x = x.astype(jnp.int32)
    pad = jnp.pad(x, ((0, 0), (3, 3), (3, 3)), mode="edge")

    wts = ([int(v) for v in _ref.GAUSS7_WEIGHTS_INT] if integer
           else [float(v) for v in _ref.GAUSS7_WEIGHTS_INT])
    horiz = None
    for k in range(7):
        term = wts[k] * pad[:, :, k:k + w]              # (B, H+6, W)
        horiz = term if horiz is None else horiz + term
    vert = None
    for k in range(7):
        term = wts[k] * horiz[:, k:k + h, :]            # (B, H, W)
        vert = term if vert is None else vert + term
    norm2 = _ref.GAUSS7_NORM * _ref.GAUSS7_NORM
    if integer:
        blur = ((vert + norm2 // 2) // norm2).astype(jnp.uint8)
    elif quantized:
        blur = jnp.floor((vert + norm2 / 2.0) / float(norm2))
    else:
        blur = vert / float(norm2)

    taps = [pad[:, 3 + dy:3 + dy + h, 3 + dx:3 + dx + w] - x
            for dx, dy in _ref.CIRCLE16]
    score = fast_score_from_taps(taps, float(threshold))
    if integer:
        score = score.astype(jnp.int16)
    return blur, score


def _nms_jnp(score: jnp.ndarray) -> jnp.ndarray:
    """Separable included-center 3x3 max over (B, H, W); cs >= max(cs,
    nbrs) iff cs >= max(nbrs), so the decision matches ref.nms3 exactly
    (the -1 constant pad is the oracle's outside-image sentinel)."""
    spad = jnp.pad(score, ((0, 0), (1, 1), (1, 1)),
                   constant_values=jnp.asarray(-1, score.dtype))
    rmax = jnp.maximum(jnp.maximum(spad[:, :-2, :], spad[:, 1:-1, :]),
                       spad[:, 2:, :])
    nmax = jnp.maximum(jnp.maximum(rmax[:, :, :-2], rmax[:, :, 1:-1]),
                       rmax[:, :, 2:])
    return (jnp.where(score >= nmax, score, jnp.zeros_like(score))
            * (score > 0).astype(score.dtype))


def _fast_blur_nms_fused_jnp(imgs: jnp.ndarray, threshold: float,
                             nms: bool, quantized: bool):
    """Interpret-free jnp fallback of the fused megakernel.

    Bit-exact against the ``ref.py`` oracle chain (tests assert it), but
    structured like the kernel rather than like the oracle — see
    ``_blur_rawscore_jnp``/``_nms_jnp``.  ~1.7x faster than the
    per-image oracle chain on CPU — the "fused" contender of the
    fused-vs-seed benchmark.
    """
    blur, score = _blur_rawscore_jnp(_cast_slab(imgs), threshold,
                                     quantized)
    if nms:
        score = _nms_jnp(score)
    return blur, score


def fast_blur_nms_batched(imgs: jnp.ndarray, threshold: float, *,
                          nms: bool = True, quantized: bool = True,
                          impl: str | None = None):
    """Fused batched frontend: (B, H, W) images -> (blur, score), each
    (B, H, W) float32, in ONE kernel launch.

    B is a flattened camera batch (the frontend stacks all cameras of a
    pyramid level); ``blur`` is the 7x7-Gaussian-smoothed image and
    ``score`` the (optionally 3x3-NMS'd) FAST-9/16 corner score map.
    This wrapper owns all padding: edge halo for the stencils plus
    zero-cost tile alignment for ragged level shapes — kernels see
    aligned tiles, callers see exact shapes.
    """
    _, h, w = imgs.shape
    if resolve_impl(impl) == "ref":
        return _fast_blur_nms_fused_jnp(imgs, threshold, nms, quantized)
    hp = (-h) % TILE_H
    wp = (-w) % TILE_W
    padded = jnp.pad(
        _cast_slab(imgs),
        ((0, 0), (FUSED_HALO, FUSED_HALO + hp), (FUSED_HALO, FUSED_HALO + wp)),
        mode="edge")
    _count_launches()
    blur, score = frontend_fused_pallas(
        padded, threshold=float(threshold), nms=bool(nms),
        quantized=bool(quantized), true_h=h, true_w=w,
        interpret=_interpret())
    return blur[:, :h, :w], score[:, :h, :w]


def fast_blur_nms_pyramid_stacked_jnp(levels, threshold: float, *,
                                      nms: bool = True,
                                      quantized: bool = True):
    """jnp mirror of the whole-pyramid kernel's ragged-padding
    semantics: every ragged level slab is edge-padded to the COMMON
    (max) canvas, the shared stencil body runs ONCE over the
    (L*B, Hc, Wc) stack, and the per-slab true shape masks outside
    pixels to the -1 NMS sentinel.

    Bit-exact against running ``_fast_blur_nms_fused_jnp`` per level
    (tests assert it): blur taps only reach 3 px past the true image —
    edge-replicated rows/cols in both schedules — and the NMS mask gives
    true-border pixels the same -1 neighbours the per-level constant pad
    does.  Kept as an INDEPENDENT oracle of the kernel's padding logic,
    not as the production fallback: on CPU the common-canvas padding
    wastes compute at 1.2x scale (measured ~1.1-1.25x the per-level
    loop's wall clock at 640x480 — the ``dense_stacked_overhead``
    benchmark row), so ``fast_blur_nms_pyramid``'s ref path loops per
    level instead — the whole-frame win is launch overhead on the
    accelerator, not CPU arithmetic.
    """
    shapes = [(int(lv.shape[1]), int(lv.shape[2])) for lv in levels]
    b = levels[0].shape[0]
    hc = max(h for h, _ in shapes)
    wc = max(w for _, w in shapes)
    x = jnp.concatenate([
        jnp.pad(_cast_slab(lv), ((0, 0), (0, hc - h), (0, wc - w)),
                mode="edge")
        for lv, (h, w) in zip(levels, shapes)], axis=0)
    blur, score = _blur_rawscore_jnp(x, threshold, quantized)
    th = jnp.asarray(np.repeat([h for h, _ in shapes], b))[:, None, None]
    tw = jnp.asarray(np.repeat([w for _, w in shapes], b))[:, None, None]
    inside = ((jnp.arange(hc)[None, :, None] < th)
              & (jnp.arange(wc)[None, None, :] < tw))
    score = jnp.where(inside, score, jnp.asarray(-1, score.dtype))
    score = (_nms_jnp(score) if nms
             else jnp.maximum(score, jnp.zeros_like(score)))
    return [(blur[l * b:(l + 1) * b, :h, :w],
             score[l * b:(l + 1) * b, :h, :w])
            for l, (h, w) in enumerate(shapes)]


def fast_blur_nms_pyramid(levels, threshold: float, *, nms: bool = True,
                          quantized: bool = True, impl: str | None = None):
    """Whole-pyramid dense stage: L ragged (B, h_l, w_l) level batches
    -> [(blur_l, score_l)] per level, ALL cameras x ALL levels in ONE
    kernel launch.

    This is the whole-frame analog of ``fast_blur_nms_batched`` (which
    launches once per level): ragged level slabs are edge-padded to a
    common tile grid, the kernel grid walks (slab, tile_i, tile_j), and
    a per-slab (true_h, true_w) table masks the padding region so small
    levels never emit spurious corners.  Together with
    ``orient_describe_pyramid`` this makes the frontend exactly TWO
    launches per quad FRAME.  The wrapper owns all padding; callers see
    exact per-level shapes.

    The ref fallback loops ``_fast_blur_nms_fused_jnp`` per level —
    bit-identical to the per-level schedule by construction and free of
    the common-canvas padding waste on CPU; the stacked jnp mirror of
    the kernel's padding logic is ``fast_blur_nms_pyramid_stacked_jnp``
    (tests pin all three against each other).
    """
    if resolve_impl(impl) == "ref":
        return [_fast_blur_nms_fused_jnp(lv, threshold, nms, quantized)
                for lv in levels]
    shapes = [(int(lv.shape[1]), int(lv.shape[2])) for lv in levels]
    b = levels[0].shape[0]
    hc, wc = dense_fe_canvas(shapes)
    with jax.named_scope("canvas"):
        flat = jnp.concatenate([
            jnp.pad(_cast_slab(lv),
                    ((0, 0), (FUSED_HALO, FUSED_HALO + hc - h),
                     (FUSED_HALO, FUSED_HALO + wc - w)), mode="edge")
            for lv, (h, w) in zip(levels, shapes)], axis=0)
    hw = jnp.asarray(np.repeat(np.asarray(shapes, np.int32), b, axis=0))
    _count_launches()
    blur, score = frontend_fused_pyramid_pallas(
        flat, hw, threshold=float(threshold), nms=bool(nms),
        quantized=bool(quantized), interpret=_interpret())
    return [(blur[l * b:(l + 1) * b, :h, :w],
             score[l * b:(l + 1) * b, :h, :w])
            for l, (h, w) in enumerate(shapes)]


def _orient_describe_jnp(raw, smoothed, xy):
    """jnp fallback of the fused sparse descriptor kernel: the per-image
    gather oracle vmapped over the camera batch.

    Bit-exact against the Pallas kernel (tests assert it): the moment /
    theta / bin math is the SAME ``ref.py`` helpers the kernel body
    calls, and the tap gather equals the kernel's selection-matmul sign
    exactly (see ``ref.lut_descriptor``).
    """
    integer = jnp.issubdtype(raw.dtype, jnp.integer)
    if smoothed is None:
        if integer:
            theta, mom = jax.vmap(lambda im, p: _ref.patch_theta_int(
                _ref.extract_patches(im, p, preserve_dtype=True)))(raw, xy)
            return theta, mom.astype(jnp.float32), None
        return jax.vmap(
            lambda im, p: _ref.patch_theta(_ref.extract_patches(im, p))
        )(raw, xy) + (None,)
    if integer:
        theta, mom, desc = jax.vmap(_ref.orient_describe_int)(
            raw, smoothed, xy)
        return theta, mom.astype(jnp.float32), desc
    return jax.vmap(_ref.orient_describe)(raw, smoothed, xy)


def _describe_canvas(shapes) -> tuple[int, int]:
    """Common (Hc, Wc) slab canvas of the sparse kernel: room for every
    level's RADIUS-padded slab and for the tile-aligned
    (WIN_H, WIN_W) window around any clamped patch start."""
    hc = max((h - 1) // 8 * 8 + WIN_H for h, _ in shapes)
    wc = max((w - 1) // 128 * 128 + WIN_W for _, w in shapes)
    return hc, wc


def _resolve_steering(mom, bins, desc16):
    """Finish the sparse kernel's outputs: theta = atan2(m01, m10) (the
    oracle's own XLA op, so bit-equal to ``ref.patch_theta``), and the
    descriptor of whichever candidate bin equals the oracle's bin."""
    theta = jnp.arctan2(mom[..., 1], mom[..., 0])
    hi = (_ref.theta_to_bin(theta) == bins[..., 1])[..., None]
    return theta, mom, jnp.where(hi, desc16[..., 8:], desc16[..., :8])


def orient_describe_batched(raw: jnp.ndarray, smoothed: jnp.ndarray | None,
                            xy: jnp.ndarray, *, impl: str | None = None):
    """Batched sparse stage of ONE pyramid level: orientation + moments
    + rBRIEF for a (B, K) block of keypoints in ONE kernel launch.

    raw/smoothed: (B, H, W) level images (smoothed = 7x7 Gaussian blur;
    None returns orientation only — ``fast.detect``'s path); xy:
    (B, K, 2) int32 level coords (clamped into the image, so top-K
    padding rows with ``valid=False`` are safe).  Returns (theta (B, K)
    float32, moments (B, K, 2) float32, desc (B, K, 8) uint32 or None).
    The Pallas path is the whole-frame kernel over one level.
    """
    if resolve_impl(impl) == "ref":
        return _orient_describe_jnp(raw, smoothed, xy)
    sm = raw if smoothed is None else smoothed
    (theta, mom, desc), = orient_describe_pyramid([raw], [sm], [xy],
                                                  impl="pallas")
    return theta, mom, None if smoothed is None else desc


def orient_describe_pyramid(raws, smootheds, xys, *,
                            impl: str | None = None):
    """Whole-frame sparse stage: per-level raw/smoothed (B, h_l, w_l)
    slab pairs plus per-level (B, K_l, 2) keypoint blocks -> per-level
    (theta, moments, desc) tuples, ALL cameras x ALL levels in ONE
    kernel launch.

    This is the whole-frame analog of ``orient_describe_batched`` (one
    launch per level): each level's keypoints are padded to a KP_BLOCK
    multiple and concatenated level-major, so every K-block is
    level-homogeneous and the kernel's index maps resolve its slab pair
    from the static block->level offsets; a per-block (true_h, true_w)
    table drives the coordinate clamp.  The wrapper owns the common-
    canvas slab padding and the K padding; callers see exact per-level
    shapes.  The jnp fallback is the per-level gather oracle — the
    per-level and whole-frame ref paths are bit-identical by
    construction.
    """
    if resolve_impl(impl) == "ref":
        return [_orient_describe_jnp(r, s, xy)
                for r, s, xy in zip(raws, smootheds, xys)]
    shapes = [(int(r_.shape[1]), int(r_.shape[2])) for r_ in raws]
    rad = _ref.RADIUS
    hc, wc = _describe_canvas(shapes)

    def slab(imgs, h, w):
        # Per-level edge pad by the patch RADIUS, then edge-replicated
        # out to the common canvas; a patch never reads past the
        # (h + 2*rad, w + 2*rad) region.
        return jnp.pad(_cast_slab(imgs),
                       ((0, 0), (rad, hc - h - rad), (rad, wc - w - rad)),
                       mode="edge")

    with jax.named_scope("canvas"):
        raw_all = jnp.concatenate(
            [slab(im, h, w) for im, (h, w) in zip(raws, shapes)], axis=0)
        sm_all = jnp.concatenate(
            [slab(im, h, w) for im, (h, w) in zip(smootheds, shapes)],
            axis=0)
    ks = [int(xy.shape[1]) for xy in xys]
    kps = [(-k) % KP_BLOCK for k in ks]
    xy_all = jnp.concatenate(
        [jnp.pad(xy.astype(jnp.int32), ((0, 0), (0, kp), (0, 0)))
         for xy, kp in zip(xys, kps)], axis=1)
    nbs = [(k + kp) // KP_BLOCK for k, kp in zip(ks, kps)]
    offsets = tuple(int(o) for o in np.cumsum([0] + nbs[:-1]))
    hw = jnp.asarray(np.repeat(np.asarray(shapes, np.int32), nbs, axis=0))
    _count_launches()
    theta, mom, desc = _resolve_steering(*describe_fused_pyramid_pallas(
        raw_all, sm_all, xy_all, hw, level_offsets=offsets,
        interpret=_interpret()))
    outs, off = [], 0
    for k, kp in zip(ks, kps):
        outs.append((theta[:, off:off + k], mom[:, off:off + k],
                     desc[:, off:off + k]))
        off += k + kp
    return outs


def _pad_rows(x: jnp.ndarray, mult: int, fill=0):
    n = x.shape[0]
    p = (-n) % mult
    if p == 0:
        return x
    pad_width = [(0, p)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill)


def _hamming_argmin_jnp(desc_l, meta_l, desc_r, meta_r,
                        row_band: float, max_disparity: float):
    """jnp oracle of the fused search-region + Hamming argmin: ONE
    definition shared by ``hamming_match`` and the fused-matcher ref
    fallbacks, so all ref paths are bit-identical by construction."""
    dist = _ref.hamming_distance_matrix(desc_l, desc_r)
    dx = meta_l[:, 0][:, None] - meta_r[:, 0][None, :]
    dy = jnp.abs(meta_l[:, 1][:, None] - meta_r[:, 1][None, :])
    mask = ((dy <= row_band) & (dx >= 0.0) & (dx <= max_disparity)
            & (meta_l[:, 2][:, None] == meta_r[:, 2][None, :])
            & (meta_l[:, 3][:, None] > 0.5)
            & (meta_r[:, 3][None, :] > 0.5))
    dist = jnp.where(mask, dist, BIG)
    best = jnp.min(dist, axis=1)
    idx = jnp.where(best >= BIG, -1,
                    jnp.argmin(dist, axis=1).astype(jnp.int32))
    return best.astype(jnp.int32), idx


def hamming_match(desc_l: jnp.ndarray, meta_l: jnp.ndarray,
                  desc_r: jnp.ndarray, meta_r: jnp.ndarray, *,
                  row_band: float, max_disparity: float,
                  impl: str | None = None):
    """Fused search-region + Hamming argmin (paper's FM front half).

    desc_*: (K, 8) uint32; meta_*: (K, 4) float32 (x, y, level, valid).
    Returns (best_dist (K,) int32 [BIG when no candidate], best_idx (K,)
    int32 [-1 when no candidate])."""
    k = desc_l.shape[0]
    if resolve_impl(impl) == "ref":
        return _hamming_argmin_jnp(desc_l, meta_l, desc_r, meta_r,
                                   row_band, max_disparity)
    # Pad to BK multiples with invalid rows (valid=0 masks them out).
    dl = _pad_rows(desc_l, BK)
    dr = _pad_rows(desc_r, BK)
    ml = _pad_rows(meta_l, BK)
    mr = _pad_rows(meta_r, BK)
    _count_launches()
    dist, idx = hamming_match_pallas(dl, ml, dr, mr, row_band=float(row_band),
                                     max_disparity=float(max_disparity),
                                     interpret=_interpret())
    dist, idx = dist[:k], idx[:k]
    return dist, jnp.where(dist >= BIG, -1, idx)


def sad_search(left_patches: jnp.ndarray, right_strips: jnp.ndarray,
               impl: str | None = None) -> jnp.ndarray:
    """(K, P, P) x (K, P, P+2R) patches -> (K, 2R+1) int32 SAD table."""
    if resolve_impl(impl) == "ref":
        return _ref.sad_search(left_patches, right_strips)
    k = left_patches.shape[0]
    lp = _pad_rows(left_patches, 128)
    rs = _pad_rows(right_strips, 128)
    _count_launches()
    return sad_search_pallas(lp, rs, interpret=_interpret())[:k]


def _pad_axis1(x: jnp.ndarray, rows: int):
    """Zero-pad axis 1 (the K/M feature axis of pair-batched arrays) out
    to ``rows``; padded meta rows carry valid=0."""
    p = rows - x.shape[1]
    if p == 0:
        return x
    pad_width = [(0, 0), (0, p)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad_width)


def _pad_fm_slab(imgs: jnp.ndarray, ry: int, rx: int) -> jnp.ndarray:
    """Edge-pad a (P, H, W) pair batch by the FM patch radii, then
    edge-replicate out to room for the tile-aligned
    (SAD_WIN_H, SAD_WIN_W) window around any clamped patch start
    (Hp % 8 == Wp % 128 == 0).  The alignment region is never read into
    a patch."""
    _, h, w = imgs.shape
    hp = max(h + 2 * ry + (-(h + 2 * ry)) % 8,
             (h - 1) // 8 * 8 + SAD_WIN_H)
    wp = max(w + 2 * rx + (-(w + 2 * rx)) % 128,
             (w - 1) // 128 * 128 + SAD_WIN_W)
    return jnp.pad(_cast_slab(imgs),
                   ((0, 0), (ry, hp - h - ry), (rx, wp - w - rx)),
                   mode="edge")


def _check_sad_window(sad_window: int, sad_range: int) -> None:
    if sad_window + 7 > SAD_WIN_H or \
            sad_window + 2 * sad_range + 127 > SAD_WIN_W:
        raise ValueError(
            f"sad_window={sad_window}, sad_range={sad_range} do not fit "
            f"the kernel's aligned ({SAD_WIN_H}, {SAD_WIN_W}) window")


def _right_t(desc_r: jnp.ndarray, meta_r: jnp.ndarray, rows: int):
    """Pad the right side to ``rows`` and transpose it to the kernels'
    lane-dense (P, 8, M) / (P, 4, M) layout."""
    return (jnp.swapaxes(_pad_axis1(desc_r, rows), 1, 2),
            jnp.swapaxes(_pad_axis1(meta_r, rows), 1, 2))


def _match_rectify_jnp(dl, ml, dr, mr, il, ir, row_band, max_disparity,
                       max_hamming, patch, sad_range):
    """Single-pair jnp fallback of the FM megakernel: the hamming
    oracle, the MatchSet index-resolution rule (``where(valid, idx,
    0)``), the edge-clamped patch gathers and the int32 SAD sweep —
    each the SAME helper the unfused path uses, so fused-ref equals
    unfused-ref by construction (and the Pallas kernel is pinned
    bit-exact against both in tests)."""
    dist, idx = _hamming_argmin_jnp(dl, ml, dr, mr, row_band,
                                    max_disparity)
    ok = (idx >= 0) & (dist <= max_hamming) & (ml[:, 3] > 0.5)
    eff = jnp.where(ok, idx, 0)
    rxy = mr[eff, :2]
    lp = _ref.gather_patches(il, ml[:, :2], patch, patch)
    rs = _ref.gather_patches(ir, rxy, patch, patch + 2 * sad_range)
    table = _ref.sad_search(lp, rs)
    return dist, idx, rxy, jnp.argmin(table, axis=1).astype(jnp.int32)


def match_rectify_fused(desc_l: jnp.ndarray, meta_l: jnp.ndarray,
                        desc_r: jnp.ndarray, meta_r: jnp.ndarray,
                        img_l: jnp.ndarray | None = None,
                        img_r: jnp.ndarray | None = None, *,
                        row_band: float, max_disparity: float,
                        max_hamming: int = 0, sad_window: int = 11,
                        sad_range: int = 5, impl: str | None = None):
    """Fused Feature Matcher dispatch: the ENTIRE FM stage of a frame —
    search-region decision + Hamming argmin + SAD rectification sweep —
    in ONE kernel launch, batched over stereo pairs (the pair axis is
    folded into the kernel grid, not vmapped).

    desc_*: (P, K, 8)/(P, M, 8) uint32; meta_*: (P, K, 4)/(P, M, 4)
    float32 rows of (x, y, level, valid); img_*: (P, H, W) level-0
    images.  Returns (dist (P, K) int32 [BIG when no candidate], idx
    (P, K) int32 [-1], rxy (P, K, 2) float32 — the effective right
    feature's coords after the ``where(valid, idx, 0)`` resolution rule,
    sad (P, K) int32 — SAD argmin in [0, 2*sad_range]; the rectified
    offset is ``sad - sad_range``).

    MATCH-ONLY mode: with ``img_l``/``img_r`` omitted the SAD half is
    skipped and only (dist, idx) return — still one launch with the
    pair-folded grid; ``stereo_match`` / ``temporal_match`` route here
    so the VO backend's matching also costs a single launch.  The
    wrapper owns all padding (K/M block alignment with valid=0 rows,
    edge-replicated image slabs); callers see exact shapes.
    """
    match_only = img_l is None
    k = desc_l.shape[1]
    if resolve_impl(impl) == "ref":
        if match_only:
            dist, idx = jax.vmap(
                lambda a, b, c, d: _hamming_argmin_jnp(
                    a, b, c, d, row_band, max_disparity)
            )(desc_l, meta_l, desc_r, meta_r)
            return dist, idx
        return jax.vmap(
            lambda a, b, c, d, e, f: _match_rectify_jnp(
                a, b, c, d, e, f, row_band, max_disparity, max_hamming,
                sad_window, sad_range)
        )(desc_l, meta_l, desc_r, meta_r, img_l, img_r)
    kp, mp = fm_rows(k, desc_r.shape[1], match_only)
    dl = _pad_axis1(desc_l, kp)
    ml = _pad_axis1(meta_l, kp)
    dr, mr = _right_t(desc_r, meta_r, mp)
    _count_launches()
    if match_only:
        dist, idx = match_fused_pallas(
            dl, ml, dr, mr, row_band=float(row_band),
            max_disparity=float(max_disparity), interpret=_interpret())
        dist, idx = dist[:, :k, 0], idx[:, :k, 0]
        return dist, jnp.where(dist >= BIG, -1, idx)
    _check_sad_window(sad_window, sad_range)
    _, h, w = img_l.shape
    ry = sad_window // 2
    dist, idx, rxy, sad = match_rectify_fused_pallas(
        dl, ml, dr, mr, meta_r[:, 0:1, :2],
        _pad_fm_slab(img_l, ry, ry),
        _pad_fm_slab(img_r, ry, ry + sad_range),
        row_band=float(row_band), max_disparity=float(max_disparity),
        max_hamming=int(max_hamming), patch=int(sad_window),
        sad_range=int(sad_range), true_h=h, true_w=w,
        interpret=_interpret())
    dist, idx = dist[:, :k, 0], idx[:, :k, 0]
    return (dist, jnp.where(dist >= BIG, -1, idx), rxy[:, :k],
            sad[:, :k, 0])


def sad_patch_search(img_l: jnp.ndarray, img_r: jnp.ndarray,
                     xy_l: jnp.ndarray, xy_r: jnp.ndarray, *,
                     sad_window: int = 11, sad_range: int = 5,
                     impl: str | None = None) -> jnp.ndarray:
    """SAD sweep with IN-KERNEL patch reads for caller-provided match
    targets (``sad_rectify``'s path): one launch replaces the host-graph
    full-image pad + 2*K ``dynamic_slice`` gather chain per pair.

    img_*: (P, H, W) level-0 images; xy_*: (P, K, 2) float32 window
    centers (left features / matched right features).  Returns the
    (P, K, 2*sad_range + 1) int32 SAD table — same contract as
    ``sad_search``, argmin taken by the caller."""
    if resolve_impl(impl) == "ref":
        return jax.vmap(
            lambda il, ir, xl, xr: _ref.sad_search(
                _ref.gather_patches(il, xl, sad_window, sad_window),
                _ref.gather_patches(ir, xr, sad_window,
                                    sad_window + 2 * sad_range))
        )(img_l, img_r, xy_l, xy_r)
    _check_sad_window(sad_window, sad_range)
    k = xy_l.shape[1]
    _, h, w = img_l.shape
    ry = sad_window // 2
    _count_launches()
    kp = _round_up(k, FM_BK)
    table = sad_fused_pallas(
        _pad_axis1(xy_l.astype(jnp.float32), kp),
        _pad_axis1(xy_r.astype(jnp.float32), kp),
        _pad_fm_slab(img_l, ry, ry),
        _pad_fm_slab(img_r, ry, ry + sad_range),
        patch=int(sad_window), sad_range=int(sad_range), true_h=h,
        true_w=w, interpret=_interpret())
    return table[:, :k]


NO_MATCH_DIST = BIG
