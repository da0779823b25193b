"""`VisualSystem` — the session API of the quad-camera visual frontend.

The paper's system is configured ONCE (rig layout, sync, FE/FM
parameters) and then streams frames through a fixed hardware schedule
(Sec. III, Fig. 4).  This module is that discipline on TPU/XLA: a
``VisualSystem`` session is built from one ``RigConfig`` (camera count,
stereo-pair layout, per-camera intrinsics, trigger/sync spec) plus one
``PipelineConfig`` (ORB parameters, kernel impl, frame schedule, match
radii) and owns everything the old free functions threaded through
every call — cfg, intrinsics, impl resolution, and the jit caches.

Entry points (each jitted once per (entry, shape) and cached on the
session — repeated same-shape calls retrace ZERO times, asserted in
tests):

    vs = VisualSystem(RigConfig.quad(intr), PipelineConfig(orb=ocfg))
    out  = vs.process_frame(images)        # (n_cameras, H, W) -> (P,) axes
    outs = vs.run(frames)                  # (T, C, H, W); schedule from cfg
    fout = vs.process_fleet(fleet_images)  # (n_rigs, C, H, W) -> (N, P)
    fseq = vs.run_fleet(fleet_frames)      # (T, n_rigs, C, H, W)

FLEET BATCHING is the scaling move of this API (the "share one datapath
across channels" discipline of the runtime-reconfigurable accelerator
in PAPERS.md §2, applied across RIGS): ``process_fleet`` folds the
leading ``(n_rigs,)`` axis into the camera/pair batch axes the kernels
already grid over — FE sees one ``(n_rigs * n_cameras,)`` camera batch,
FM one ``(n_rigs * n_pairs,)`` pair batch — so an N-rig fleet frame
still costs exactly THREE kernel launches (1 dense FE + 1 sparse FE +
1 fused FM), the same budget as a single rig (CI-gated via
``launch_gate/fleet_frame_*``), and is bit-exact against the per-rig
loop.  With ``PipelineConfig.rig_shard_axis`` set and a
``distributed.sharding.use_sharding`` mesh installed, the fleet axis is
additionally ``shard_map``'d over that mesh axis (3 launches per
device).

GRACEFUL DEGRADATION (the robustness half of the paper's sync/mux
machinery): ``process_frame`` / ``process_fleet`` accept a per-camera
liveness ``camera_mask`` — dead camera slabs are sanitized to zero
before the kernels and every validity field they touch is gated off, so
a rig with a dead camera degrades to its surviving stereo pairs in the
SAME 3 launches (CI-gated).  Per-frame ``timestamps`` run the rig's
desync policy (``RigConfig.desync_policy``: raise | drop_frame |
degrade); the streaming fleet service (``repro.serving``) layers
watchdog supervision, fault detection and bucketed batching on top of
these hooks.

MIGRATION MAP (the old free functions survive as thin deprecation
shims, bit-exact against these paths):

    process_quad_frame(im, cfg, intr)    -> VisualSystem.process_frame(im)
    process_stereo_frame(l, r, cfg, intr)-> .process_frame(stack([l, r]))
                                            (2-camera rig; drop pair axis)
    run_sequence(frames, cfg, intr)      -> .run(frames)  (schedule=
                                            "sequential")
    run_sequence_pipelined(...)          -> .run(frames)  (schedule=
                                            "pipelined")
    extract_pair(l, r, cfg)              -> .extract(stack([l, r]))
    match_pair(l, r, fl, fr, cfg, intr)  -> .match_pair(l, r, fl, fr)
    stereo_match(fl, fr, cfg)            -> .stereo_match(fl, fr)
    temporal_match(fa, fb, cfg, radius)  -> .temporal_match(fa, fb, ...)
    sad_rectify(l, r, fl, fr, m, cfg, i) -> .sad_rectify(l, r, fl, fr, m)
    ops.set_default_impl(impl)           -> PipelineConfig(impl=...) or
                                            ops.use_impl(impl) (scoped)
    ops.reset_launch_count/launch_count  -> ops.launch_audit() or
                                            VisualSystem.traced_launches
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import matching, orb, pyramid
from repro.core import sync as sync_mod
from repro.core.rig import DesyncError, RigConfig
from repro.core.types import (CameraIntrinsics, FeatureSet,
                              LocalizationOutput, LocalizationState,
                              MatchSet, ORBConfig, PoseSet, StereoOutput)
from repro.distributed import sharding
from repro.kernels import ops
from repro import localization
from repro.localization import pose as pose_solver

_SCHEDULES = ("sequential", "pipelined")
_PRECISIONS = ("f32", "uint8")


class DesyncDecision(typing.NamedTuple):
    """Outcome of the rig's desync policy for one frame's time tags.

    ``action`` is one of ``"ok"`` (process normally — includes the
    legacy software-sync log-only path), ``"raise"``, ``"drop_frame"``
    or ``"degrade"``; ``camera_mask`` is the (n_cameras,) bool keep-mask
    for the degrade action, else None."""

    desync: float
    action: str
    camera_mask: np.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Everything about HOW frames are processed (the rig says WHAT).

    ``impl`` resolves the kernel implementation once for the whole
    session ("ref" | "pallas" | None = backend default) instead of the
    old per-call / global ``ops.set_default_impl`` threading.
    ``schedule`` picks the ``run`` discipline: "sequential" (FE+FM per
    frame in order) or "pipelined" (Fig. 4: FE(t) overlaps FM(t-1), one
    frame of latency hidden by the drain step).  ``rig_shard_axis``
    names the mesh axis ``process_fleet`` / ``run_fleet`` shard the
    rig dimension over when a ``use_sharding`` mesh is installed.
    ``precision`` selects the image datapath: "f32" (default) keeps
    float32 slabs; "uint8" is the paper's 8-bit datapath end-to-end —
    uint8 pyramid slabs, int32 fixed-point blur accumulation, int16
    FAST scores, int32 patch moments and int8 descriptor selection —
    cutting resident slab VMEM 4x in the same 3-launch budget.  The
    uint8 path requires ``ORBConfig.quantized`` and uint8 input frames
    (validated eagerly); FAST keypoints and descriptors are bit-exact
    against the quantized f32 path.

    ``localize`` turns on the localization backend
    (``repro.localization``): ``process_frame`` / ``process_fleet`` /
    ``run`` / ``run_fleet`` then return a ``LocalizationOutput``
    (frontend fields + rig-frame 3-D points + relative ego-motion
    ``PoseSet``) instead of a bare ``StereoOutput``.  The backend adds
    exactly ONE kernel launch per frame (the batched temporal matcher;
    triangulation and the robust Procrustes solve are jnp) — a
    localized frame is <= 4 launches, CI-gated.  It defaults OFF so
    frontend-only sessions keep their output type, launch budget, and
    bit-exactness pins unchanged.
    """

    orb: ORBConfig = ORBConfig()
    impl: str | None = None
    schedule: str = "sequential"
    temporal_radius: float = 48.0
    temporal_radius_y: float | None = None
    rig_shard_axis: str | None = None
    precision: str = "f32"
    localize: bool = False

    def __post_init__(self):
        if self.schedule not in _SCHEDULES:
            raise ValueError(
                f"schedule must be one of {_SCHEDULES}, "
                f"got {self.schedule!r}")
        if self.impl not in (None, "ref", "pallas"):
            raise ValueError(
                f"impl must be None, 'ref' or 'pallas', got {self.impl!r}")
        if self.precision not in _PRECISIONS:
            raise ValueError(
                f"precision must be one of {_PRECISIONS}, "
                f"got {self.precision!r}")
        if self.precision == "uint8" and not self.orb.quantized:
            raise ValueError(
                "precision='uint8' requires ORBConfig.quantized=True: "
                "the integer datapath IS the quantized fixed-point "
                "pipeline held in uint8 slabs (a float Gaussian is not "
                "representable in a uint8 level)")


class VisualSystem:
    """One configured rig + pipeline, with jitted cached entry points.

    The session resolves impl once (``PipelineConfig.impl``), owns the
    jit cache for every entry point (``trace_count`` observes retraces),
    validates frame shapes eagerly with clear errors, and applies the
    rig's sync policy to per-frame time tags (``desync_log`` /
    ``DesyncError``).

    ``counters`` counts what the session did: ``traces.<entry>`` (jit
    traces of an entry's program) and ``calls.process_frame``.
    ``process_frame`` records profiler spans: ``repro.process_frame``
    (with ``call``, the call number) around ``repro.validate``,
    ``repro.frame_call`` (with ``h2d_bytes``: the bytes of a host NumPy
    frame handed to the program, 0 for a device array, and the frame's
    kernel grids from static shapes, ``ops.frame_grids``: ``fe_tiles``,
    ``fe_tiles_real``, ``fm_pairs``, and ``ops.describe_grid``:
    ``describe_bf16_slots``) and ``repro.localize_call``; with no
    profiler running a span costs one check.
    """

    def __init__(self, rig: RigConfig,
                 pipe: PipelineConfig | None = None) -> None:
        if not isinstance(rig, RigConfig):
            raise TypeError(f"rig must be a RigConfig, got {type(rig)!r}")
        self.rig = rig
        self.pipe = pipe if pipe is not None else PipelineConfig()
        if not isinstance(self.pipe, PipelineConfig):
            raise TypeError(
                f"pipe must be a PipelineConfig, got {type(self.pipe)!r}")
        # Impl is resolved ONCE, at construction (None -> the ambient
        # use_impl context / process default / backend default), so a
        # session's kernel path is pinned for its lifetime — later
        # context or global flips cannot silently miss the jit cache.
        self.impl: str = ops.resolve_impl(self.pipe.impl)
        self._jitted: dict = {}
        self.counters: collections.Counter = collections.Counter()
        cfg = self.pipe.orb
        slab_dtype = (np.uint8 if self.pipe.precision == "uint8"
                      else np.float32)
        self._frame_grids = {
            **ops.frame_grids(pyramid.level_shapes(cfg), rig.n_cameras,
                              rig.n_pairs, sum(cfg.features_per_level())),
            **ops.describe_grid(cfg.features_per_level(), rig.n_cameras,
                                slab_dtype)}
        # Bounded health log: one spread per checked frame; a streaming
        # session at 30 fps would otherwise grow this without limit.
        self.desync_log: "collections.deque[float]" = collections.deque(
            maxlen=4096)
        # Localization memory: the previous processed frame's state per
        # entry key ("frame" / ("fleet", n_rigs)) — only written when
        # PipelineConfig.localize is on.  Callers that manage their own
        # cross-batch state (the serving tier) pass ``prev=`` instead.
        self._loc_state: dict = {}

    # -- jit cache ---------------------------------------------------------

    @staticmethod
    def _entry(key) -> str:
        """The entry name of a jit-cache key: the key, or its first
        element for keys that carry static parameters."""
        return key if isinstance(key, str) else key[0]

    def _jit(self, key, fn):
        """Jit ``fn`` once per entry-point key; jax.jit's own cache then
        keys on argument shapes.  The program is named after the entry
        (HLO module ``jit_<entry>``).  The wrapper counts traces (a
        python side effect that only fires while tracing) so tests can
        assert repeated same-shape calls retrace zero times."""
        if key not in self._jitted:
            entry = self._entry(key)

            def counted(*args):
                self.counters[f"traces.{entry}"] += 1
                return fn(*args)
            counted.__name__ = counted.__qualname__ = entry
            self._jitted[key] = jax.jit(counted)
        return self._jitted[key]

    def program(self, key):
        """The jitted program of entry ``key``, once an entry point has
        built it.  A TPU profiler trace names each device op by its HLO
        instruction alone, without the ``jax.named_scope`` path; the
        ``op_name`` metadata of
        ``program(key).lower(*args).compile().as_text()`` carries it,
        so a trace reader maps ops to scopes through this text."""
        return self._jitted[key]

    def trace_count(self, key) -> int:
        """How many times entry point ``key`` has been traced (i.e. how
        many distinct input shapes it has compiled for)."""
        return self.counters[f"traces.{self._entry(key)}"]

    @staticmethod
    def _span(name: str, **stats):
        """A ``repro.<name>`` profiler span carrying ``stats``."""
        return jax.profiler.TraceAnnotation(f"repro.{name}", **stats)

    # -- shape / sync validation (eager, outside jit) ----------------------

    def _check_images(self, images, *, fleet: bool, sequence: bool,
                      what: str | None = None) -> None:
        want_nd = 3 + int(fleet) + int(sequence)
        shape = tuple(images.shape)
        if what is None:
            what = (("run_fleet" if sequence else "process_fleet") if fleet
                    else ("run" if sequence else "process_frame"))
        if len(shape) != want_nd:
            raise ValueError(
                f"{what} expects a rank-{want_nd} array "
                f"{'(T, ' if sequence else '('}"
                f"{'n_rigs, ' if fleet else ''}n_cameras, H, W); got "
                f"shape {shape}")
        c, h, w = shape[-3], shape[-2], shape[-1]
        if c != self.rig.n_cameras:
            raise ValueError(
                f"{what}: camera axis is {c} but the rig has "
                f"{self.rig.n_cameras} cameras")
        cfg = self.pipe.orb
        if (h, w) != (cfg.height, cfg.width):
            raise ValueError(
                f"{what}: image shape ({h}, {w}) does not match "
                f"PipelineConfig.orb ({cfg.height}, {cfg.width})")
        if sequence and shape[0] == 0:
            raise ValueError(
                f"{what}: empty sequence (T == 0); the "
                f"{self.pipe.schedule!r} schedule needs at least one "
                "frame (the pipelined prologue/drain is defined for "
                "T >= 1)")
        self._check_dtype(images, what)

    def _check_dtype(self, images, what: str) -> None:
        """Eager dtype validation against the session's configured
        precision — a float frame silently entering a uint8 session (or
        an integer frame a float one) would otherwise produce garbage
        scores deep inside the kernels instead of an error here."""
        dtype = np.dtype(getattr(images, "dtype", np.asarray(images).dtype))
        precision = self.pipe.precision
        if precision == "uint8":
            if dtype != np.uint8:
                raise TypeError(
                    f"{what}: images have dtype {dtype.name} but this "
                    "session is configured with "
                    "PipelineConfig(precision='uint8') — the integer "
                    "datapath needs uint8 frames.  Quantize with "
                    "np.round(np.clip(images, 0, 255)).astype(np.uint8) "
                    "or build the session with precision='f32'.")
        elif not np.issubdtype(dtype, np.floating):
            raise TypeError(
                f"{what}: images have dtype {dtype.name} but this "
                "session is configured with "
                "PipelineConfig(precision='f32') — pass float frames "
                "(e.g. images.astype(np.float32)) or build the session "
                "with precision='uint8' to run the integer datapath.")

    def desync_decision(self, timestamps) -> DesyncDecision:
        """Apply the rig's sync + desync policies to one frame's camera
        time tags WITHOUT raising — the inspectable form ``check_desync``
        and the serving supervisor build on.

        The tag spread is the float64 single-frame evaluation of
        ``sync.max_desync`` over the (n_cameras,) stamp vector
        (``sync.frame_desync`` — epoch-scale stamps have 128 s float32
        spacing, so this must not round-trip through float32); it is
        appended to ``desync_log``.  A spread within ``rig.max_desync``
        is ``"ok"``.  Beyond it, ``rig.desync_policy`` decides: the
        default (None) keeps the legacy split — hardware rigs get
        ``"raise"`` (the paper's Sec. III-A 0-cycle guarantee), software
        rigs log and stay ``"ok"`` — while an explicit policy applies to
        both sync disciplines uniformly (``"degrade"`` also computes the
        median-cluster camera keep-mask)."""
        ts = np.asarray(timestamps, dtype=np.float64).reshape(-1)
        if ts.shape[0] != self.rig.n_cameras:
            raise ValueError(
                f"expected {self.rig.n_cameras} per-camera timestamps, "
                f"got {ts.shape[0]}")
        desync = sync_mod.frame_desync(ts)
        self.desync_log.append(desync)
        if desync <= self.rig.max_desync:
            return DesyncDecision(desync, "ok")
        policy = self.rig.desync_policy
        if policy is None:
            policy = ("raise" if self.rig.sync_policy == "hardware"
                      else "ok")
        if policy == "degrade":
            return DesyncDecision(
                desync, "degrade",
                sync_mod.desync_camera_mask(ts, self.rig.max_desync))
        return DesyncDecision(desync, policy)

    def _desync_error(self, desync: float, what: str = "") -> DesyncError:
        return DesyncError(
            f"{what}{self.rig.sync_policy}-sync rig saw {desync:.3e}s "
            f"inter-camera desync (tolerance {self.rig.max_desync:.3e}s)"
            ": time tags must come from the unified trigger clock "
            "(paper Sec. III-A)")

    def check_desync(self, timestamps) -> float:
        """Legacy strict form of ``desync_decision``: returns the tag
        spread (seconds, logged to ``desync_log``) and raises
        ``DesyncError`` when the rig's policy resolves to ``"raise"``."""
        decision = self.desync_decision(timestamps)
        if decision.action == "raise":
            raise self._desync_error(decision.desync)
        return decision.desync

    # -- engine (pure, jit-able; impl threaded explicitly) -----------------

    def _flat_pair_indices(self, n_rigs: int):
        """Left/right camera indices of every pair of every rig in the
        flattened ``(n_rigs * n_cameras,)`` camera batch."""
        c = self.rig.n_cameras
        left = np.asarray(self.rig.left_cams, np.int32)
        right = np.asarray(self.rig.right_cams, np.int32)
        offs = np.arange(n_rigs, dtype=np.int32)[:, None] * c
        return (jnp.asarray((offs + left[None, :]).reshape(-1)),
                jnp.asarray((offs + right[None, :]).reshape(-1)))

    def _fm_intr(self, n_rigs: int):
        """Shared ``CameraIntrinsics`` when the rig is homogeneous (the
        scalar fast path, bit-identical to the legacy functions), else a
        per-pair ``fx * baseline`` column tiled across the fleet."""
        if self.rig.homogeneous_intrinsics:
            return self.rig.intrinsics[0]
        fxb = np.asarray([float(ic.fx) * float(ic.baseline)
                          for ic in self.rig.pair_intrinsics], np.float32)
        return jnp.asarray(np.tile(fxb, n_rigs)[:, None])

    def _fe_flat(self, images, n_rigs: int, impl):
        """FE stage over the flat camera batch: ONE dense + ONE sparse
        launch for every camera of every rig at every pyramid level."""
        feats = orb.extract_features_batched(images, self.pipe.orb,
                                             impl=impl,
                                             precision=self.pipe.precision)
        li, ri = self._flat_pair_indices(n_rigs)
        feat_l = jax.tree.map(lambda x: x[li], feats)
        feat_r = jax.tree.map(lambda x: x[ri], feats)
        return images[li], images[ri], feat_l, feat_r

    @jax.named_scope("stereo")
    def _fm_flat(self, carry, n_rigs: int, impl) -> StereoOutput:
        """FM stage over the flat pair batch: ONE fused matcher launch
        whose grid folds every pair of every rig."""
        imgs_l, imgs_r, feat_l, feat_r = carry
        matches, depth = matching.match_pair_fused(
            imgs_l, imgs_r, feat_l, feat_r, self.pipe.orb,
            self._fm_intr(n_rigs), impl=impl)
        return StereoOutput(feat_l, feat_r, matches, depth)

    def _core_flat(self, flat, n_rigs: int, impl,
                   mask_flat=None) -> StereoOutput:
        """The 3-launch datapath over the flat (n_rigs * n_cameras,)
        camera batch, with optional graceful degradation: a per-camera
        liveness mask sanitizes dead slabs to zero BEFORE the kernels
        (NaN/garbage from a dead sensor never enters the fused launches)
        and gates every validity field AFTER them — a rig with a dead
        camera degrades to its surviving stereo pairs, in the SAME 3
        launches (masking is elementwise jnp, not a kernel), and
        all-true masks are bit-exact identity."""
        if mask_flat is not None:
            flat = jnp.where(mask_flat[:, None, None], flat,
                             jnp.zeros_like(flat))
        out = self._fm_flat(self._fe_flat(flat, n_rigs, impl), n_rigs,
                            impl)
        if mask_flat is not None:
            li, ri = self._flat_pair_indices(n_rigs)
            ml, mr = mask_flat[li], mask_flat[ri]
            out = matching.mask_stereo_output(out, ml, mr, ml & mr)
        return out

    def _frame_core(self, images, impl, camera_mask=None) -> StereoOutput:
        """(n_cameras, H, W) -> StereoOutput with (n_pairs,) axes; a
        fleet-of-one view of the same 3-launch datapath.  ``camera_mask``
        ((n_cameras,) bool, optional) masks dead cameras through the
        batch axes — see ``_core_flat``."""
        mask = (None if camera_mask is None
                else jnp.asarray(camera_mask).reshape(-1).astype(bool))
        return self._core_flat(images, 1, impl, mask)

    def _fleet_core(self, images, impl, camera_mask=None) -> StereoOutput:
        """(n_rigs, n_cameras, H, W) -> StereoOutput with
        (n_rigs, n_pairs) axes; the rig axis is folded into the kernels'
        camera/pair batch axes, so the whole fleet frame still costs 3
        launches — degraded or not (``camera_mask``: (n_rigs, n_cameras)
        bool, optional)."""
        n = images.shape[0]
        flat = images.reshape((n * self.rig.n_cameras,) + images.shape[2:])
        mask = (None if camera_mask is None
                else jnp.asarray(camera_mask).astype(bool).reshape(-1))
        out = self._core_flat(flat, n, impl, mask)
        return jax.tree.map(
            lambda x: x.reshape((n, self.rig.n_pairs) + x.shape[1:]), out)

    def _run_core(self, frames, impl, fleet: bool) -> StereoOutput:
        if self.pipe.schedule == "pipelined":
            return self._run_pipelined(frames, impl, fleet)
        per_frame = self._fleet_core if fleet else self._frame_core
        def body(_, frame):
            return None, per_frame(frame, impl)
        _, outs = jax.lax.scan(body, None, frames)
        return outs

    def _run_pipelined(self, frames, impl, fleet: bool) -> StereoOutput:
        """Fig. 4 schedule: FE(t) overlaps FM(t-1) inside one scan step;
        the final frame's FM runs in a drain step, so outputs cover all
        T frames aligned to ``frames``.  T == 1 degenerates to prologue
        + drain (an empty scan) and equals the sequential schedule;
        T == 0 is rejected eagerly in ``run``/``run_fleet`` with a
        clear error instead of the old bare in-trace ``assert``."""
        t_total = int(frames.shape[0])
        n_pairs = self.rig.n_pairs

        def fe(frame):
            if fleet:
                n = frame.shape[0]
                flat = frame.reshape((n * self.rig.n_cameras,)
                                     + frame.shape[2:])
                return self._fe_flat(flat, n, impl)
            return self._fe_flat(frame, 1, impl)

        def fm(carry):
            n = carry[0].shape[0] // n_pairs
            out = self._fm_flat(carry, n, impl)
            if fleet:
                out = jax.tree.map(
                    lambda x: x.reshape((n, n_pairs) + x.shape[1:]), out)
            return out

        carry0 = fe(frames[0])

        def body(carry, frame):
            # FM(t-1) and FE(t): no data dependence -> XLA may overlap.
            out = fm(carry)
            return fe(frame), out

        carry_last, outs = jax.lax.scan(body, carry0, frames[1:])
        last = fm(carry_last)
        outs = jax.tree.map(
            lambda xs, x: jnp.concatenate([xs, x[None]], axis=0),
            outs, last)
        if outs.matches.valid.shape[0] != t_total:  # static shape check
            raise RuntimeError(
                f"pipelined schedule produced "
                f"{outs.matches.valid.shape[0]} outputs for {t_total} "
                "frames — drain/prologue accounting is broken")
        return outs

    # -- localization engine (pure, jit-able) ------------------------------

    def _temporal_radii(self) -> tuple[float, float]:
        rx = float(self.pipe.temporal_radius)
        ry = (rx if self.pipe.temporal_radius_y is None
              else float(self.pipe.temporal_radius_y))
        return rx, ry

    @jax.named_scope("localize")
    def _loc_flat(self, out: StereoOutput, prev: LocalizationState,
                  n_rigs: int, impl):
        """Backend stage over the FLAT (n_rigs * n_pairs,) pair batch:
        rig-frame triangulation (jnp, 0 launches), ONE fused temporal
        match launch folding every pair of every rig, and the vmapped
        robust Procrustes solve (jnp).  Returns (points (B*P, K, 3),
        PoseSet with (n_rigs,) axes)."""
        p = self.rig.n_pairs
        k = out.features_l.valid.shape[-1]
        xy = out.features_l.xy.reshape((n_rigs, p, k, 2))
        z = out.depth.depth.reshape((n_rigs, p, k))
        pts = localization.rig_points(xy, z, self.rig)
        pts_flat = pts.reshape((n_rigs * p, k, 3))
        curr = LocalizationState(
            desc=out.features_l.desc,
            meta=matching._meta(out.features_l),
            points=pts_flat,
            valid=out.features_l.valid & out.depth.valid)
        rx, ry = self._temporal_radii()
        with jax.named_scope("temporal_match"):
            pp, cp, w = pose_solver.temporal_correspondences(
                prev, curr, self.pipe.orb, rx, ry, impl)
        with jax.named_scope("pose_solve"):
            pose = pose_solver.solve_pose_batched(
                pp.reshape((n_rigs, p * k, 3)),
                cp.reshape((n_rigs, p * k, 3)),
                w.reshape((n_rigs, p * k)))
        return pts_flat, pose

    def _localize_frame(self, out: StereoOutput, prev: LocalizationState,
                        impl):
        """Frame view of ``_loc_flat``: (P,) axes in, scalar pose out."""
        pts, pose = self._loc_flat(out, prev, 1, impl)
        return pts, jax.tree.map(lambda x: x[0], pose)

    def _localize_fleet(self, out: StereoOutput, prev: LocalizationState,
                        impl):
        """Fleet view: (n, P, ...) axes in, (n,) pose out — the rig
        axis folds into the temporal matcher's pair grid and the solve's
        vmap, so localizing a whole fleet is still ONE extra launch."""
        n = out.features_l.valid.shape[0]
        p, k = self.rig.n_pairs, out.features_l.valid.shape[-1]
        flat = jax.tree.map(
            lambda x: x.reshape((n * p,) + x.shape[2:]), out)
        prev_flat = jax.tree.map(
            lambda x: x.reshape((n * p,) + x.shape[2:]), prev)
        pts, pose = self._loc_flat(flat, prev_flat, n, impl)
        return pts.reshape((n, p, k, 3)), pose

    def _run_loc(self, frames, impl, fleet: bool) -> LocalizationOutput:
        """Localized sequence: the frontend scan (3 launches per step)
        plus ONE temporal-match launch for ALL T-1 frame transitions of
        all rigs (time folds into the matcher's pair grid exactly like
        the fleet axis), then the (T-1)*n_rigs-way batched solve.
        ``pose`` row 0 is identity + invalid (no predecessor)."""
        outs = self._run_core(frames, impl, fleet)
        shaped = outs if fleet else jax.tree.map(lambda x: x[:, None],
                                                 outs)
        feat_l = shaped.features_l
        t_total, n = feat_l.valid.shape[0], feat_l.valid.shape[1]
        p, k = self.rig.n_pairs, feat_l.valid.shape[-1]
        pts = localization.rig_points(feat_l.xy, shaped.depth.depth,
                                      self.rig)      # (T, n, P, K, 3)
        meta = matching._meta(feat_l)
        valid = feat_l.valid & shaped.depth.valid

        def invalid_pose(lead):
            return PoseSet(
                rotation=jnp.broadcast_to(jnp.eye(3, dtype=jnp.float32),
                                          lead + (3, 3)),
                translation=jnp.zeros(lead + (3,), jnp.float32),
                inliers=jnp.zeros(lead, jnp.int32),
                valid=jnp.zeros(lead, bool))

        if t_total == 1:
            pose = invalid_pose((1, n))
        else:
            b = (t_total - 1) * n

            def flat(x, sl):
                return x[sl].reshape((b * p,) + x.shape[3:])

            def state(sl):
                return LocalizationState(
                    desc=flat(feat_l.desc, sl), meta=flat(meta, sl),
                    points=flat(pts, sl), valid=flat(valid, sl))

            rx, ry = self._temporal_radii()
            pp, cp, w = pose_solver.temporal_correspondences(
                state(slice(None, -1)), state(slice(1, None)),
                self.pipe.orb, rx, ry, impl)
            pose = pose_solver.solve_pose_batched(
                pp.reshape((b, p * k, 3)), cp.reshape((b, p * k, 3)),
                w.reshape((b, p * k)))
            pose = jax.tree.map(
                lambda x: x.reshape((t_total - 1, n) + x.shape[1:]),
                pose)
            pose = jax.tree.map(
                lambda first, rest: jnp.concatenate([first, rest]),
                invalid_pose((1, n)), pose)
        if not fleet:
            pts = pts[:, 0]
            pose = jax.tree.map(lambda x: x[:, 0], pose)
        return LocalizationOutput(outs, pts, pose)

    def _resolve_prev(self, prev, key, out: StereoOutput, what: str
                      ) -> LocalizationState:
        """Previous-frame state for a localized entry: the caller's
        explicit ``prev`` (shape-validated eagerly), else the session's
        stored state for this entry key, else the all-invalid zero state
        (session start — the solve degenerates to identity+invalid)."""
        k = out.features_l.valid.shape[-1]
        n_rigs = None if key == "frame" else key[1]
        if prev is None:
            prev = self._loc_state.get(key)
        if prev is None:
            return localization.zero_state(self.rig.n_pairs, k, n_rigs)
        if not isinstance(prev, LocalizationState):
            raise TypeError(
                f"{what}: prev must be a LocalizationState "
                f"(see repro.localization.state_from), got "
                f"{type(prev)!r}")
        lead = ((self.rig.n_pairs,) if n_rigs is None
                else (n_rigs, self.rig.n_pairs))
        want = lead + (k, 3)
        got = tuple(prev.points.shape)
        if got != want:
            raise ValueError(
                f"{what}: prev.points shape {got} does not match {want} "
                "— the state must come from the same rig layout and "
                "feature budget (and, for fleets, the same n_rigs)")
        return prev

    def reset_localization(self) -> None:
        """Forget all cross-frame localization state: the next
        ``process_frame`` / ``process_fleet`` behaves like a session
        start (identity + invalid pose).  Call between unrelated
        sequences so a stale previous frame cannot leak into a pose."""
        self._loc_state.clear()

    # -- frame / sequence entry points -------------------------------------

    def _coerce_camera_mask(self, camera_mask, n_rigs: int | None,
                            what: str) -> np.ndarray | None:
        """Validate a caller camera mask eagerly: (n_cameras,) bool for
        a frame, (n_rigs, n_cameras) for a fleet; returns np.bool_."""
        if camera_mask is None:
            return None
        mask = np.asarray(camera_mask, dtype=bool)
        want = ((self.rig.n_cameras,) if n_rigs is None
                else (n_rigs, self.rig.n_cameras))
        if mask.shape != want:
            raise ValueError(
                f"{what}: camera_mask shape {mask.shape} does not match "
                f"{want} (per-camera liveness"
                f"{'' if n_rigs is None else ' per rig'})")
        return mask

    def _frame_desync_mask(self, timestamps,
                           camera_mask: np.ndarray | None):
        """Resolve one frame's desync policy into (dropped, camera_mask):
        raise raises, drop_frame -> (True, _), degrade ANDs the median-
        cluster keep-mask into the caller's liveness mask."""
        decision = self.desync_decision(timestamps)
        if decision.action == "raise":
            raise self._desync_error(decision.desync)
        if decision.action == "drop_frame":
            return True, camera_mask
        if decision.action == "degrade":
            keep = decision.camera_mask
            camera_mask = (keep if camera_mask is None
                           else camera_mask & keep)
        return False, camera_mask

    def process_frame(self, images, timestamps=None, camera_mask=None,
                      prev: LocalizationState | None = None
                      ) -> StereoOutput | LocalizationOutput | None:
        """One rig frame: (n_cameras, H, W) -> StereoOutput with leading
        (n_pairs,) axes, in exactly 3 kernel launches (2 FE + 1 FM).
        With ``PipelineConfig.localize`` the return is a
        ``LocalizationOutput`` (adds rig-frame 3-D points and the
        relative pose vs the previous processed frame) in <= 4 launches;
        ``prev`` overrides the session-held previous-frame state
        (``repro.localization.state_from``), e.g. for callers that
        interleave several streams through one session.

        ``timestamps`` (optional, (n_cameras,) seconds) runs the rig's
        per-frame desync policy (``desync_decision``) before dispatch:
        ``raise`` raises ``DesyncError``, ``drop_frame`` returns None
        (the frame is NOT processed), ``degrade`` masks the offending
        cameras.  ``camera_mask`` (optional, (n_cameras,) bool) marks
        dead cameras: their slabs are sanitized to zero before the
        kernels and every validity field they touch is gated off, so
        the rig degrades to its surviving stereo pairs — still 3
        launches, bit-exact on the surviving cameras.
        """
        self.counters["calls.process_frame"] += 1
        with self._span("process_frame",
                        call=self.counters["calls.process_frame"]):
            with self._span("validate"):
                self._check_images(images, fleet=False, sequence=False)
                camera_mask = self._coerce_camera_mask(camera_mask, None,
                                                       "process_frame")
                if timestamps is not None:
                    dropped, camera_mask = self._frame_desync_mask(
                        timestamps, camera_mask)
                    if dropped:
                        return None
            h2d = images.nbytes if isinstance(images, np.ndarray) else 0
            with self._span("frame_call", h2d_bytes=h2d,
                            **self._frame_grids):
                if camera_mask is None:
                    out = self._jit(
                        "process_frame",
                        lambda im: self._frame_core(im, self.impl))(images)
                else:
                    out = self._jit(
                        "process_frame_masked",
                        lambda im, cm: self._frame_core(im, self.impl, cm))(
                            images, jnp.asarray(camera_mask))
            if not self.pipe.localize:
                return out
            with self._span("localize_call"):
                prev_state = self._resolve_prev(prev, "frame", out,
                                                "process_frame")
                pts, pose = self._jit(
                    "localize_frame",
                    lambda o, pv: self._localize_frame(o, pv, self.impl))(
                        out, prev_state)
                lout = LocalizationOutput(out, pts, pose)
                self._loc_state["frame"] = localization.state_from(lout)
            return lout

    def process_fleet(self, images, timestamps=None, camera_mask=None,
                      prev: LocalizationState | None = None
                      ) -> StereoOutput | LocalizationOutput:
        """One frame from EVERY rig of a fleet: (n_rigs, n_cameras, H, W)
        -> StereoOutput with leading (n_rigs, n_pairs) axes — still 3
        kernel launches total, bit-exact against the per-rig loop.

        ``images`` may also be a SEQUENCE of per-rig (n_cameras, H, W)
        frames; mismatched per-rig shapes (e.g. rigs with different
        camera counts) raise an eager, descriptive ``ValueError`` here
        instead of an opaque jit trace failure deep in the kernels.

        ``timestamps`` ((n_rigs, n_cameras), optional) applies the desync
        policy PER RIG: ``raise`` raises naming the rig, ``drop_frame``
        masks the whole offending rig out of the batch (fleet shapes are
        static — a dropped rig cannot leave the array), ``degrade``
        masks its offending cameras.  ``camera_mask``
        ((n_rigs, n_cameras) bool, optional) marks dead cameras; masked
        rigs/cameras degrade to their surviving pairs in the same 3
        launches.

        With ``PipelineConfig.rig_shard_axis`` set and a
        ``use_sharding`` mesh installed, the rig axis is sharded over
        that mesh axis via ``shard_map`` (n_rigs must divide evenly;
        degraded — masked — fleets currently take the unsharded path).

        With ``PipelineConfig.localize`` the return is a
        ``LocalizationOutput`` with (n_rigs,) pose axes — the temporal
        matcher folds rigs into its pair grid and the solve vmaps, so
        the WHOLE fleet localizes in one extra launch (<= 4 total).
        ``prev`` ((n_rigs, ...) ``LocalizationState``) overrides the
        session-held state — the serving tier re-buckets rigs between
        batches, so it assembles per-rig state explicitly (localized
        fleets take the unsharded path).
        """
        images = self._coerce_fleet_images(images, "process_fleet")
        self._check_images(images, fleet=True, sequence=False)
        n_rigs = int(images.shape[0])
        camera_mask = self._coerce_camera_mask(camera_mask, n_rigs,
                                               "process_fleet")
        if timestamps is not None:
            ts = np.asarray(timestamps, dtype=np.float64)
            if ts.shape != (n_rigs, self.rig.n_cameras):
                raise ValueError(
                    f"process_fleet: timestamps shape {ts.shape} does "
                    f"not match ({n_rigs}, {self.rig.n_cameras})")
            rows = (np.ones((n_rigs, self.rig.n_cameras), dtype=bool)
                    if camera_mask is None else camera_mask.copy())
            for r in range(n_rigs):
                try:
                    dropped, row = self._frame_desync_mask(
                        ts[r], rows[r])
                except DesyncError:
                    raise self._desync_error(
                        sync_mod.frame_desync(ts[r]),
                        what=f"fleet rig {r}: ") from None
                rows[r] = False if dropped else row
            camera_mask = rows
        if camera_mask is None:
            sharded = (None if self.pipe.localize
                       else self._fleet_sharded("process_fleet",
                                                self._fleet_core))
            if sharded is not None:
                return sharded(images)
            out = self._jit(
                "process_fleet",
                lambda im: self._fleet_core(im, self.impl))(images)
        else:
            out = self._jit(
                "process_fleet_masked",
                lambda im, cm: self._fleet_core(im, self.impl, cm))(
                    images, jnp.asarray(camera_mask))
        if not self.pipe.localize:
            return out
        key = ("fleet", n_rigs)
        prev_state = self._resolve_prev(prev, key, out, "process_fleet")
        pts, pose = self._jit(
            "localize_fleet",
            lambda o, pv: self._localize_fleet(o, pv, self.impl))(
                out, prev_state)
        lout = LocalizationOutput(out, pts, pose)
        self._loc_state[key] = localization.state_from(lout)
        return lout

    def _coerce_fleet_images(self, images, what: str):
        """Fleet inputs arrive either as one stacked array or as a
        sequence of per-rig frames.  Stacking is only defined when every
        rig shares one (n_cameras, H, W) shape — mismatched rigs (the
        classic mixed quad/stereo fleet footgun) fail HERE with the
        per-rig shapes spelled out, not as an XLA trace error."""
        if isinstance(images, (list, tuple)) or (
                hasattr(images, "dtype") and images.dtype == object):
            shapes = [tuple(np.shape(x)) for x in images]
            if len(set(shapes)) > 1:
                raise ValueError(
                    f"{what}: rigs have mismatched frame shapes "
                    f"{shapes}; every rig in one fleet batch must share "
                    f"the same (n_cameras, H, W) = "
                    f"({self.rig.n_cameras}, {self.pipe.orb.height}, "
                    f"{self.pipe.orb.width}).  Rigs with different "
                    "camera counts need their own session (one "
                    "RigConfig per layout) — the serving queue buckets "
                    "per layout for exactly this reason.")
            images = jnp.stack([jnp.asarray(x) for x in images])
        return images

    def run(self, frames) -> StereoOutput | LocalizationOutput:
        """A frame sequence (T, n_cameras, H, W) -> StereoOutput with
        leading (T, n_pairs) axes, under ``PipelineConfig.schedule``.
        With ``localize`` on: a ``LocalizationOutput`` whose pose rows
        are the per-step relative motion (row 0 identity+invalid);
        sequences are self-contained — they neither read nor write the
        ``process_frame`` cross-call state."""
        self._check_images(frames, fleet=False, sequence=True)
        if self.pipe.localize:
            return self._jit(
                "run_loc",
                lambda f: self._run_loc(f, self.impl, False))(frames)
        return self._jit(
            "run",
            lambda f: self._run_core(f, self.impl, False))(frames)

    def run_fleet(self, frames) -> StereoOutput | LocalizationOutput:
        """A fleet sequence (T, n_rigs, n_cameras, H, W) -> StereoOutput
        with leading (T, n_rigs, n_pairs) axes; both schedules fold the
        rig axis into the batched kernels (3 launches per scan step).
        With ``localize`` on: a ``LocalizationOutput`` with
        (T, n_rigs) pose axes (row 0 identity+invalid; unsharded)."""
        self._check_images(frames, fleet=True, sequence=True)
        if self.pipe.localize:
            return self._jit(
                "run_fleet_loc",
                lambda f: self._run_loc(f, self.impl, True))(frames)
        sharded = self._fleet_sharded(
            "run_fleet", lambda f, impl: self._run_core(f, impl, True))
        if sharded is not None:
            return sharded(frames)
        return self._jit(
            "run_fleet",
            lambda f: self._run_core(f, self.impl, True))(frames)

    def _fleet_sharded(self, entry: str, core):
        """shard_map'd jitted fleet entry when a mesh context carrying
        ``rig_shard_axis`` is installed, else None.  ``core`` takes
        (array, impl) with the rig axis leading (axis 0 for
        process_fleet; run_fleet shards axis 1 of (T, n_rigs, ...))."""
        axis = self.pipe.rig_shard_axis
        ctx = sharding.current_ctx()
        if axis is None or ctx is None or axis not in dict(ctx.mesh.shape):
            return None
        # The key leads with the plain entry name, so trace_count(entry)
        # observes sharded retraces too.
        key = (entry, "sharded", axis, ctx.mesh)
        if key not in self._jitted:
            rig_dim = 1 if entry == "run_fleet" else 0
            self._jit(key, sharding.shard_over(
                lambda x: core(x, self.impl), ctx.mesh, axis,
                arg_axis=rig_dim))
        return self._jitted[key]

    # -- feature / matcher entry points ------------------------------------

    def extract(self, images) -> FeatureSet:
        """FE only: (n_cameras, H, W) -> FeatureSet with a leading
        (n_cameras,) axis, in 2 launches (1 dense + 1 sparse)."""
        self._check_images(images, fleet=False, sequence=False,
                           what="extract")
        return self._jit(
            "extract",
            lambda im: orb.extract_features_batched(
                im, self.pipe.orb, impl=self.impl))(images)

    def match_pair(self, img_l, img_r, feat_l: FeatureSet,
                   feat_r: FeatureSet):
        """FM stage for ONE explicit stereo pair (a pair-batch-of-one
        view of the fused megakernel): returns (MatchSet, DepthSet).
        Depth uses the first pair's left-camera intrinsics."""
        intr = self.rig.pair_intrinsics[0]
        def core(il, ir, fl, fr):
            matches, depth = matching.match_pair_fused(
                il[None], ir[None],
                jax.tree.map(lambda x: x[None], fl),
                jax.tree.map(lambda x: x[None], fr),
                self.pipe.orb, intr, impl=self.impl)
            return jax.tree.map(lambda x: x[0], (matches, depth))
        return self._jit("match_pair", core)(img_l, img_r, feat_l, feat_r)

    def stereo_match(self, feat_l: FeatureSet,
                     feat_r: FeatureSet) -> MatchSet:
        """Best Hamming match in the strip-like search region
        (Sec. II-C1) via the fused dispatch's match-only mode — one
        launch."""
        cfg = self.pipe.orb
        def core(fl, fr):
            dist, idx = ops.match_rectify_fused(
                fl.desc[None], matching._meta(fl)[None],
                fr.desc[None], matching._meta(fr)[None],
                row_band=float(cfg.row_band),
                max_disparity=float(cfg.max_disparity),
                impl=self.impl)
            return matching._match_set(dist[0], idx[0], fl, cfg)
        return self._jit("stereo_match", core)(feat_l, feat_r)

    def temporal_match(self, feat_a: FeatureSet, feat_b: FeatureSet,
                       search_radius: float | None = None,
                       search_radius_y: float | None = None) -> MatchSet:
        """Frame-to-frame matching for the VO backend (match-only fused
        mode, one launch) over a rectangular +-radius window; radii
        default to ``PipelineConfig.temporal_radius`` /
        ``temporal_radius_y`` (y falls back to the x radius)."""
        cfg = self.pipe.orb
        rx = (self.pipe.temporal_radius if search_radius is None
              else float(search_radius))
        ry = search_radius_y
        if ry is None:
            ry = (self.pipe.temporal_radius_y
                  if self.pipe.temporal_radius_y is not None else rx)
        ry = float(ry)
        def core(fa, fb):
            meta_a = matching._meta(fa)
            # Reuse the [0, max_disparity] window as [-rx, +rx] by
            # shifting the left x coordinate.
            meta_a = meta_a.at[:, 0].add(rx)
            dist, idx = ops.match_rectify_fused(
                fa.desc[None], meta_a[None],
                fb.desc[None], matching._meta(fb)[None],
                row_band=ry, max_disparity=2.0 * rx, impl=self.impl)
            return matching._match_set(dist[0], idx[0], fa, cfg)
        return self._jit(("temporal_match", rx, ry), core)(feat_a, feat_b)

    def sad_rectify(self, img_l, img_r, feat_l: FeatureSet,
                    feat_r: FeatureSet, matches: MatchSet):
        """SAD rectification + disparity/depth (Sec. II-C2, III-D) for
        one explicit pair, with IN-KERNEL patch reads
        (``ops.sad_patch_search`` — one launch).  Depth uses the first
        pair's left-camera intrinsics."""
        cfg = self.pipe.orb
        intr = self.rig.pair_intrinsics[0]
        def core(il, ir, fl, fr, m):
            xy_l = fl.xy
            xy_r = fr.xy[m.right_index]
            table = ops.sad_patch_search(
                il[None], ir[None], xy_l[None], xy_r[None],
                sad_window=cfg.sad_window, sad_range=cfg.sad_range,
                impl=self.impl)[0]
            best = (jnp.argmin(table, axis=1).astype(jnp.float32)
                    - float(cfg.sad_range))
            return matching._depth_set(xy_l[:, 0], xy_r, best, m, cfg,
                                       intr)
        return self._jit("sad_rectify", core)(img_l, img_r, feat_l,
                                              feat_r, matches)

    # -- audit --------------------------------------------------------------

    ENTRY_POINTS = ("process_frame", "process_fleet", "extract",
                    "match", "run", "run_fleet")

    def entry_core(self, entry: str, impl: str = "pallas"):
        """The PURE traceable core of one entry point — the exact
        function graph the jitted public entry dispatches, with impl
        pinned and all eager validation / state plumbing stripped, so
        audit tooling can ``jax.make_jaxpr`` / ``jax.eval_shape`` it
        over abstract shapes (no data, no execution).

        ``process_frame`` / ``process_fleet`` cores accept an optional
        trailing camera-mask argument (the DEGRADED graph — same 3
        launches, masking is elementwise jnp).  On a ``localize``
        session the frame / fleet / run cores trace the FULL localized
        graph (frontend + temporal matcher + solve) against the zero
        previous state, which shares the launch graph of every steady
        state.  ``match`` is the FM stage alone over a flat
        (n_pairs,)-leading pair batch (``launch_gate/fm_frame_*``).

        Both ``traced_launches`` (the runtime CI gate numbers) and
        ``repro.analysis`` (the static auditor) trace THESE cores, so
        static counts reconcile with the benchmark rows by
        construction."""
        k = self.pipe.orb.max_features

        def frame_core(im, cm=None):
            out = self._frame_core(im, impl, cm)
            if not self.pipe.localize:
                return out
            prev = localization.zero_state(self.rig.n_pairs, k)
            return self._localize_frame(out, prev, impl)

        def fleet_core(im, cm=None):
            out = self._fleet_core(im, impl, cm)
            if not self.pipe.localize:
                return out
            prev = localization.zero_state(self.rig.n_pairs, k,
                                           int(im.shape[0]))
            return self._localize_fleet(out, prev, impl)

        def run_core(f, fleet):
            if self.pipe.localize:
                return self._run_loc(f, impl, fleet)
            return self._run_core(f, impl, fleet)

        def match_core(il, ir, fl, fr):
            n_rigs = max(1, il.shape[0] // self.rig.n_pairs)
            return self._fm_flat((il, ir, fl, fr), n_rigs, impl)

        cores = {
            "process_frame": frame_core,
            "process_fleet": fleet_core,
            "extract": lambda im: orb.extract_features_batched(
                im, self.pipe.orb, impl=impl,
                precision=self.pipe.precision),
            "match": match_core,
            "run": lambda f: run_core(f, False),
            "run_fleet": lambda f: run_core(f, True),
        }
        try:
            return cores[entry]
        except KeyError:
            raise ValueError(
                f"entry_core supports {sorted(cores)}, "
                f"got {entry!r}") from None

    def traced_launches(self, entry: str, *args) -> int:
        """Trace ``entry``'s core (``entry_core``) shape-only under
        impl='pallas' and return the number of kernel launches in the
        traced graph — the deterministic schedule number the CI launch
        gates enforce (3 per frame / fleet frame), independent of the
        session's impl.  ``process_frame`` / ``process_fleet`` accept an
        optional second camera-mask argument so the DEGRADED budget
        (also 3 — masking is elementwise jnp, not a launch) is gateable
        too.  On a ``localize`` session the frame/fleet/run entries
        trace the FULL localized graph (frontend + temporal matcher +
        solve), so the <= 4 localized budget is gateable the same
        way."""
        core = self.entry_core(entry, impl="pallas")
        with ops.launch_audit() as audit:
            jax.eval_shape(core, *args)
        return audit.count


def session_for(cfg: ORBConfig, intr: CameraIntrinsics | None,
                impl: str | None, n_cameras: int = 2,
                schedule: str = "sequential") -> VisualSystem:
    """Session cache backing the legacy free-function shims: one
    ``VisualSystem`` per (ORBConfig, intrinsics, impl, layout), so
    repeated shim calls reuse jit caches exactly like a held session.
    Cameras pair up in the legacy [L, R, L, R, ...] order.  ``impl`` is
    resolved BEFORE the cache lookup, preserving the legacy functions'
    per-call resolution: an ``ops.use_impl`` scope or a
    ``set_default_impl`` flip selects a different cached session rather
    than silently reusing one pinned to the old impl."""
    return _session_for(cfg, intr, ops.resolve_impl(impl), n_cameras,
                        schedule)


@functools.lru_cache(maxsize=128)
def _session_for(cfg, intr, impl, n_cameras, schedule) -> VisualSystem:
    pairs = tuple((2 * i, 2 * i + 1) for i in range(n_cameras // 2))
    rig = RigConfig(n_cameras=n_cameras, pairs=pairs,
                    intrinsics=intr if intr is not None
                    else CameraIntrinsics())
    return VisualSystem(rig, PipelineConfig(orb=cfg, impl=impl,
                                            schedule=schedule))
