"""Benchmark harness — one function per paper table.

  table1_latency_split   Tab. I   frontend vs backend time, 3 modes
  table_fe_fm_ratio      Fig. 4   FE vs FM stage latency (multiplexing
                                  rationale: steady period = max(2FE, FM))
  table2_module_cost     Tab. II  per-module cost split (FE ~ 2/3 claim)
  table3_accuracy        Tab. III hardware path (Pallas) vs software
                                  (jnp oracle) + word-length ablation
  table4_throughput      Tab. IV  fps at 640x480 / 1280x720 on this CPU
                                  + modeled TPU-v5e roofline fps
  table_fused_vs_seed    PR 1     fused batched frontend (one dense
                                  launch per level for all 4 cameras) vs
                                  the seed per-camera-per-op dispatch:
                                  wall clock + traced launch counts
  table_describe_fused_vs_gather
                         PR 2     fused sparse descriptor stage (one
                                  orientation+rBRIEF launch per level,
                                  LUT-binned steering) vs the seed
                                  host-graph per-keypoint gathers
  table_whole_frame_vs_per_level
                         PR 3     whole-frame schedule (ONE dense + ONE
                                  sparse launch per frame for all
                                  cameras x levels, ragged levels padded
                                  to a common tile grid) vs the
                                  per-level schedule (2 launches per
                                  level): wall clock + traced launch
                                  counts; also emits the launch_gate
                                  rows the CI regression gate
                                  (check_launches.py) enforces
  table_fm_fused_vs_unfused
                         PR 4     fused FM megakernel (ONE launch per
                                  frame: Hamming match + in-kernel SAD
                                  patch reads, pair axis in the grid)
                                  vs the unfused two-kernel +
                                  host-graph-gather schedule: wall
                                  clock + traced launch counts
  table_fleet            PR 5     `VisualSystem.process_fleet`: an
                                  N-rig fleet frame folded into the
                                  batched kernels (3 launches total,
                                  same as one rig) vs the per-rig
                                  python loop; emits the
                                  launch_gate/fleet_frame_* rows CI
                                  enforces
  table_service          PR 6     streaming fleet service under fault
                                  injection; emits the degraded-fleet
                                  launch_gate rows
  table_precision        PR 7     uint8 integer datapath vs f32: wall
                                  clock + computed resident FM slab
                                  bytes/pair (4x cut), and the
                                  launch_gate/u8_* rows CI enforces
                                  (uint8 frame/fleet frame == 3
                                  launches)
  table_localization     PR 8     depth + ego-motion backend closed
                                  against scene ground truth: ATE/RPE
                                  accuracy_gate rows CI enforces for
                                  f32 AND uint8, plus the
                                  launch_gate/loc_* rows (localized
                                  frame <= 3 frontend + 1 backend
                                  launches)
  table_failover         PR 9     multi-host failover: host_down
                                  redistribution + guarded-dispatch
                                  episode (frames dropped, rigs moved,
                                  retries) and a kill-and-recover
                                  episode through a crash-consistent
                                  snapshot (recovery wall clock,
                                  snapshot bytes); emits the
                                  launch_gate/restored_fleet_frame_*
                                  rows CI enforces

Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--out PATH]
Prints CSV rows ``table,name,value,unit,note`` and writes them to a
JSON artifact (default BENCH_frontend.json) for perf-trajectory
tracking in CI.

Timing discipline: every benchmark output is ``jax.block_until_ready``'d
— including outputs produced OUTSIDE ``_bench`` that later feed a timed
function — so no reported ms silently includes an async dependency.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (CameraIntrinsics, ORBConfig, PipelineConfig,
                        RigConfig, VisualSystem, backend,
                        extract_features, pipeline_schedule)
from repro.core import pyramid
from repro.data import scenes
from repro.kernels import ops, ref


def _stereo_vs(ocfg, intr=None, impl=None):
    intr = intr if intr is not None else CameraIntrinsics()
    return VisualSystem(RigConfig.stereo(intr),
                        PipelineConfig(orb=ocfg, impl=impl))


def _stereo_frame(vs, img_l, img_r):
    out = vs.process_frame(jnp.stack([img_l, img_r]))
    return jax.tree.map(lambda x: x[0], out)

ROWS = []


def emit(table, name, value, unit="", note=""):
    ROWS.append((table, name, value, unit, note))
    print(f"{table},{name},{value},{unit},{note}", flush=True)


def _bench(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters, out


def _bench_median(fn, *args, iters=5, reps=3):
    """Median of ``reps`` independent ``_bench`` means.  For contenders
    whose wall clocks are within scheduler noise of each other (the
    fused-vs-unfused FM table), a single mean can flip the reported
    speedup by 2x on a loaded host; the median of repeats keeps the
    perf-trajectory artifact rows trustworthy."""
    return sorted(_bench(fn, *args, iters=iters)[0]
                  for _ in range(reps))[reps // 2]


def _scene(h, w, n=300, seed=11):
    cfg = scenes.SceneConfig(height=h, width=w, n_points=n, seed=seed,
                             baseline=0.3)
    frames, poses, intr = scenes.render_sequence(cfg, 3)
    return frames, poses, intr, cfg


# ---------------------------------------------------------------------------

def table1_latency_split(quick=False):
    """Tab. I analog: share of localization time spent in the visual
    frontend for three backend modes.  Paper: 54.8% (SLAM), 86.7% (VIO),
    84.6% (Registration)."""
    h, w = (120, 160) if quick else (240, 320)
    frames, poses, intr, _ = _scene(h, w)
    ocfg = ORBConfig(height=h, width=w, max_features=256, n_levels=2,
                     max_disparity=64)

    vs = _stereo_vs(ocfg, intr)
    fe_fm = lambda l, r: _stereo_frame(vs, l, r)   # session-jitted
    t_front, out0 = _bench(fe_fm, frames[0, 0], frames[0, 1])
    out1 = jax.block_until_ready(fe_fm(frames[1, 0], frames[1, 1]))

    def make_backend(refine, iters):
        def run(prev_feats, prev_depth, curr_feats, curr_depth):
            tm = vs.temporal_match(prev_feats, curr_feats)
            pts_p = backend.triangulate(prev_feats, prev_depth, intr)
            pts_c = backend.triangulate(curr_feats, curr_depth, intr)
            idx = tm.right_index
            wgt = (tm.valid & prev_depth.valid
                   & curr_depth.valid[idx]).astype(jnp.float32)
            return backend.estimate_relative_pose(
                pts_p, pts_c[idx], wgt, curr_feats.xy[idx], intr,
                refine=refine, robust_iters=iters)
        return jax.jit(run)

    modes = {"slam": make_backend(True, 3),
             "vio": make_backend(False, 1),
             "registration": make_backend(True, 6)}
    for mode, fn in modes.items():
        t_back, _ = _bench(fn, out0.features_l, out0.depth,
                           out1.features_l, out1.depth)
        share = t_front / (t_front + t_back)
        emit("table1", f"frontend_share_{mode}", round(100 * share, 1),
             "%", f"front {t_front*1e3:.1f}ms back {t_back*1e3:.1f}ms")
    emit("table1", "paper_frontend_share",
         "54.8/86.7/84.6", "%", "slam/vio/registration (paper Tab. I)")


def table_fe_fm_ratio(quick=False):
    """Fig. 4 rationale: FM latency ~ 2x FE at 640x480 in the paper
    (7.28 vs 14.59 ms) -> two channels share one FE."""
    h, w = (240, 320) if quick else (480, 640)
    frames, poses, intr, _ = _scene(h, w)
    ocfg = ORBConfig(height=h, width=w, max_features=512, n_levels=2,
                     max_disparity=96)
    fe = jax.jit(lambda im: extract_features(im, ocfg))
    t_fe, featl = _bench(fe, frames[0, 0])
    featr = jax.block_until_ready(fe(frames[0, 1]))
    vs = _stereo_vs(ocfg, intr)
    fm = lambda l, r, fl, fr: vs.match_pair(l, r, fl, fr)
    t_fm, _ = _bench(fm, frames[0, 0], frames[0, 1], featl, featr)
    emit("fig4", "t_fe_ms", round(t_fe * 1e3, 2), "ms", "one image")
    emit("fig4", "t_fm_ms", round(t_fm * 1e3, 2), "ms", "stereo pair")
    emit("fig4", "fm_over_fe", round(t_fm / t_fe, 2), "x",
         "paper: 2.0 (7.28 vs 14.59 ms)")
    sched = pipeline_schedule(100, t_fe * 1e3, t_fm * 1e3)
    emit("fig4", "steady_period_ms", round(sched["steady_period_ms"], 2),
         "ms", "frame-multiplexed pipeline")
    emit("fig4", "serial_period_ms", round(sched["serial_period_ms"], 2),
         "ms", "no pipelining")
    emit("fig4", "pipeline_speedup",
         round(sched["serial_period_ms"] / sched["steady_period_ms"], 2),
         "x", "Fig. 4 schedule vs serial")


def table2_module_cost(quick=False):
    """Tab. II analog: per-module share of frontend cost.  The FPGA
    spends ~2/3 of its resources on FE; we report the wall-time split of
    the same module boundary plus per-module times."""
    h, w = (240, 320) if quick else (480, 640)
    frames, poses, intr, _ = _scene(h, w)
    ocfg = ORBConfig(height=h, width=w, max_features=512, n_levels=2,
                     max_disparity=96)
    from repro.core import brief, fast
    img = frames[0, 0]

    mods = {}
    t, levels = _bench(jax.jit(lambda i: pyramid.build_pyramid(i, ocfg)),
                       img)
    mods["resize"] = t
    t, _ = _bench(jax.jit(lambda i: ops.fast_score_map(
        i, float(ocfg.fast_threshold))), levels[0])
    mods["fast_detect"] = t
    xy = jnp.asarray(np.stack([np.random.RandomState(0).randint(
        16, w - 16, 512), np.random.RandomState(1).randint(
        16, h - 16, 512)], 1).astype(np.int32))
    t, _ = _bench(jax.jit(lambda i, p: fast.orientations(i, p)),
                  levels[0], xy)
    mods["orientation"] = t
    t, sm = _bench(jax.jit(lambda i: ops.gaussian_blur7(i)), levels[0])
    mods["smoothing"] = t
    th = jnp.zeros((512,))
    t, _ = _bench(jax.jit(lambda s, p, a: brief.describe(s, p, a)),
                  sm, xy, th)
    mods["descriptor"] = t
    vs = _stereo_vs(ocfg, intr)
    fe = jax.jit(lambda i: extract_features(i, ocfg))
    featl = jax.block_until_ready(fe(frames[0, 0]))
    featr = jax.block_until_ready(fe(frames[0, 1]))
    t, m = _bench(vs.stereo_match, featl, featr)
    mods["stereo_match"] = t
    t, _ = _bench(vs.sad_rectify, frames[0, 0], frames[0, 1],
                  featl, featr, m)
    mods["sad_rectify"] = t

    total = sum(mods.values())
    fe_mods = ("resize", "fast_detect", "orientation", "smoothing",
               "descriptor")
    fe_share = sum(mods[k] for k in fe_mods) / total
    for k, v in mods.items():
        emit("table2", f"{k}_ms", round(v * 1e3, 3), "ms",
             "FE" if k in fe_mods else "FM")
    emit("table2", "fe_share", round(100 * fe_share, 1), "%",
         "paper: FE ~ 2/3 of frontend resources")


def table3_accuracy(quick=False):
    """Tab. III: hardware path vs software reference over frames.
    Paper error: < 0.3% on counts; ours is bit-exact (0.0%).  Plus the
    word-length (quantized vs float) ablation."""
    h, w = (120, 160) if quick else (240, 320)
    n_frames = 2 if quick else 6
    cfg = scenes.SceneConfig(height=h, width=w, n_points=200, seed=5,
                             baseline=0.3)
    frames, _, intr = scenes.render_sequence(cfg, n_frames)
    ocfg = ORBConfig(height=h, width=w, max_features=256, n_levels=2,
                     max_disparity=64)
    tot = {"feat": [0, 0], "match": [0, 0], "depth": [0, 0]}
    coord_eq = [0, 0]
    vs_hw = _stereo_vs(ocfg, intr, impl="pallas")
    vs_sw = _stereo_vs(ocfg, intr, impl="ref")
    for t in range(n_frames):
        hw = _stereo_frame(vs_hw, frames[t, 0], frames[t, 1])
        sw = _stereo_frame(vs_sw, frames[t, 0], frames[t, 1])
        tot["feat"][0] += int(hw.features_l.count())
        tot["feat"][1] += int(sw.features_l.count())
        tot["match"][0] += int(hw.matches.count())
        tot["match"][1] += int(sw.matches.count())
        tot["depth"][0] += int(hw.depth.count())
        tot["depth"][1] += int(sw.depth.count())
        eq = np.asarray(hw.features_l.xy) == np.asarray(sw.features_l.xy)
        coord_eq[0] += int(eq.all(-1).sum())
        coord_eq[1] += int(eq.shape[0])
    for k, (a, b) in tot.items():
        err = 100.0 * abs(a - b) / max(b, 1)
        emit("table3", f"{k}_hw_vs_sw", f"{a}/{b}", "count",
             f"err {err:.2f}% (paper <0.3%)")
    emit("table3", "coord_agreement",
         round(100 * coord_eq[0] / coord_eq[1], 2), "%",
         "paper: 99.7/98.2/96.8%")

    q = ocfg
    f = ORBConfig(**{**q.__dict__, "quantized": False})
    hwq = _stereo_frame(_stereo_vs(q, intr), frames[0, 0], frames[0, 1])
    hwf = _stereo_frame(_stereo_vs(f, intr), frames[0, 0], frames[0, 1])
    emit("table3", "wordlen_feat_counts",
         f"{int(hwq.features_l.count())}/{int(hwf.features_l.count())}",
         "count", "8-bit vs float datapath (ablation)")


def table4_throughput(quick=False):
    """Tab. IV: frontend fps at the paper's two resolutions on this
    host's CPU.  Paper: 69 fps @640x480, 50.7 fps @1280x720 (FPGA);
    9 fps (TX1), 15 fps (i7) @720p.  No device number is modelled
    here: a chip rate comes only from a run on the chip."""
    resolutions = [(480, 640)] + ([] if quick else [(720, 1280)])
    for h, w in resolutions:
        frames, poses, intr, _ = _scene(h, w, n=400)
        ocfg = ORBConfig(height=h, width=w, max_features=1000,
                         n_levels=2, max_disparity=96)
        vs = _stereo_vs(ocfg, intr)
        step = lambda l, r: _stereo_frame(vs, l, r)
        t, _ = _bench(step, frames[0, 0], frames[0, 1], iters=3)
        emit("table4", f"cpu_fps_{w}x{h}", round(1.0 / t, 1), "fps",
             "this host, one stereo pair")
    emit("table4", "paper_fpga_fps", "69/50.7", "fps",
         "640x480 / 1280x720")
    emit("table4", "paper_baselines_720p", "TX1 9, i7 15", "fps",
         "paper Tab. IV")


def table_fused_vs_seed(quick=False):
    """Tentpole regression number: the fused batched frontend (ONE
    launch per pyramid level for all 4 cameras, blur + FAST + NMS in one
    VMEM pass) vs the seed dispatch (per camera: separate blur and FAST
    passes over the same pixels plus eight host-graph NMS slices).

    Wall clock is measured on the jnp fallback (interpret-free CPU
    path); kernel-launch counts are traced under the Pallas impl and are
    the deterministic, machine-independent half of the comparison.
    """
    resolutions = [(480, 640)] + ([] if quick else [(720, 1280)])
    for h, w in resolutions:
        rng = np.random.RandomState(7)
        imgs = jnp.asarray(rng.randint(0, 256, (4, h, w)).astype(np.float32))
        ocfg = ORBConfig(height=h, width=w, n_levels=2)
        thr = float(ocfg.fast_threshold)

        def seed_frontend(images, impl="ref"):
            """Seed schedule: python-loop over cameras and levels,
            separate blur / FAST launches, jnp-slice NMS."""
            outs = []
            for c in range(images.shape[0]):
                for lv in pyramid.build_pyramid(images[c], ocfg):
                    score = ops.fast_score_map(lv, thr, impl=impl)
                    score = ref.nms3(score)
                    blur = ops.gaussian_blur7(lv, quantized=True, impl=impl)
                    outs.append((blur, score))
            return outs

        def fused_frontend(images, impl="ref"):
            """Fused schedule: one batched launch per level."""
            outs = []
            for lv in pyramid.build_pyramid_batched(images, ocfg):
                outs.append(ops.fast_blur_nms_batched(
                    lv, thr, nms=True, quantized=True, impl=impl))
            return outs

        iters = 3 if (h, w) == (720, 1280) else 5
        t_seed, _ = _bench(jax.jit(seed_frontend), imgs, iters=iters)
        t_fused, _ = _bench(jax.jit(fused_frontend), imgs, iters=iters)
        res = f"{w}x{h}"
        emit("fused", f"seed_ms_{res}", round(t_seed * 1e3, 2), "ms",
             "4 cams x 2 levels, per-image dispatch (jnp)")
        emit("fused", f"fused_ms_{res}", round(t_fused * 1e3, 2), "ms",
             "4 cams x 2 levels, batched fused (jnp)")
        emit("fused", f"speedup_{res}", round(t_seed / t_fused, 2), "x",
             "seed / fused wall clock")

        # Launch counts: trace-only (no kernel execution) under Pallas.
        with ops.launch_audit() as audit:
            jax.eval_shape(lambda im: seed_frontend(im, impl="pallas"), imgs)
        n_seed = audit.count
        with ops.launch_audit() as audit:
            jax.eval_shape(lambda im: fused_frontend(im, impl="pallas"), imgs)
        n_fused = audit.count
        emit("fused", f"launches_seed_{res}", n_seed, "kernels",
             "4 cams x 2 levels x (blur + fast)")
        emit("fused", f"launches_fused_{res}", n_fused, "kernels",
             "1 fused launch per level")


def table_describe_fused_vs_gather(quick=False):
    """Tentpole regression number for the sparse stage: the fused
    orientation + rBRIEF dispatch (ONE launch per level for all 4
    cameras, LUT-binned steering, gather-free taps) vs the seed schedule
    (vmapped per-keypoint 31x31 dynamic_slice gathers + per-keypoint
    cos/sin exact steering on the host graph).

    Wall clock is measured on the jnp paths (interpret-free CPU);
    launch counts are traced under the Pallas impl — the deterministic
    half.
    """
    from repro.core import fast
    resolutions = [(480, 640)] + ([] if quick else [(720, 1280)])
    for h, w in resolutions:
        rng = np.random.RandomState(7)
        imgs = jnp.asarray(rng.randint(0, 256, (4, h, w)).astype(np.float32))
        ocfg = ORBConfig(height=h, width=w, n_levels=2, max_features=1000)
        res = f"{w}x{h}"

        # Dense stage + top-K once, outside the timed region: both
        # contenders consume identical (raw, smoothed, xy) level inputs.
        levels = pyramid.build_pyramid_batched(imgs, ocfg)
        ks = ocfg.features_per_level()
        staged = []
        for imgs_l, k_l in zip(levels, ks):
            smoothed, score = ops.fast_blur_nms_batched(
                imgs_l, float(ocfg.fast_threshold), impl="ref")
            xy, _, _ = jax.vmap(
                lambda s, k=k_l: fast.select_topk(s, k, ocfg.border))(score)
            staged.append((jax.block_until_ready(imgs_l),
                           jax.block_until_ready(smoothed),
                           jax.block_until_ready(xy)))

        def gather_stage(staged_levels):
            """Seed schedule: host-graph patch gathers, exact steering."""
            outs = []
            for raw_l, sm_l, xy_l in staged_levels:
                theta = jax.vmap(lambda im, p: ref.patch_theta(
                    ref.extract_patches(im, p))[0])(raw_l, xy_l)
                desc = jax.vmap(ref.describe_steered)(sm_l, xy_l, theta)
                outs.append((theta, desc))
            return outs

        def fused_stage(staged_levels, impl="ref"):
            """Fused schedule: one sparse dispatch per level."""
            return [ops.orient_describe_batched(raw_l, sm_l, xy_l, impl=impl)
                    for raw_l, sm_l, xy_l in staged_levels]

        iters = 3 if (h, w) == (720, 1280) else 5
        t_gather, _ = _bench(jax.jit(gather_stage), staged, iters=iters)
        t_fused, _ = _bench(jax.jit(fused_stage), staged, iters=iters)
        emit("describe", f"gather_ms_{res}", round(t_gather * 1e3, 2), "ms",
             "4 cams x 2 levels, vmapped 31x31 gathers + exact steering")
        emit("describe", f"fused_ms_{res}", round(t_fused * 1e3, 2), "ms",
             "4 cams x 2 levels, batched LUT dispatch (jnp)")
        emit("describe", f"speedup_{res}", round(t_gather / t_fused, 2), "x",
             "gather / fused wall clock")

        # Launch counts: trace-only (no kernel execution) under Pallas.
        with ops.launch_audit() as audit:
            jax.eval_shape(lambda s: fused_stage(s, impl="pallas"), staged)
        emit("describe", f"launches_fused_{res}", audit.count,
             "kernels", "1 sparse launch per level (gather path: 0 "
             "kernels, all host graph)")


def table_whole_frame_vs_per_level(quick=False):
    """Tentpole regression number for the whole-frame schedule: ONE
    dense + ONE sparse launch per quad FRAME for all cameras x all
    pyramid levels (ragged level slabs padded to a common tile grid,
    masked by true shape) vs the per-level schedule (2 launches per
    level — ``orb.extract_features_per_level``, the PR-2 pipeline).

    Wall clock is measured on the jnp paths (interpret-free CPU), where
    both schedules run the same per-level arithmetic — the whole-frame
    ref fallback deliberately loops per level because the stacked
    common-canvas pass wastes ~20% CPU compute on ragged-level padding
    (the stacked row below quantifies that, pinning the decision).  The
    whole-frame win is the traced launch count — the deterministic half,
    enforced in CI by ``benchmarks.check_launches`` via the launch_gate
    rows emitted here.
    """
    from repro.core import extract_features_per_level
    from repro.core import orb
    resolutions = [(480, 640)] + ([] if quick else [(720, 1280)])
    for h, w in resolutions:
        rng = np.random.RandomState(7)
        imgs = jnp.asarray(rng.randint(0, 256, (4, h, w)).astype(np.float32))
        ocfg = ORBConfig(height=h, width=w, n_levels=2, max_features=1000)
        res = f"{w}x{h}"

        iters = 3 if (h, w) == (720, 1280) else 5
        t_per, _ = _bench(
            jax.jit(lambda im: extract_features_per_level(im, ocfg,
                                                          impl="ref")),
            imgs, iters=iters)
        t_whole, _ = _bench(
            jax.jit(lambda im: orb.extract_features_batched(im, ocfg,
                                                            impl="ref")),
            imgs, iters=iters)
        emit("whole_frame", f"per_level_ms_{res}", round(t_per * 1e3, 2),
             "ms", "4 cams x 2 levels, 2 dispatches per level (jnp)")
        emit("whole_frame", f"whole_frame_ms_{res}",
             round(t_whole * 1e3, 2), "ms",
             "4 cams x 2 levels, 1 dense + 1 sparse dispatch (jnp)")
        emit("whole_frame", f"speedup_{res}", round(t_per / t_whole, 2),
             "x", "per-level / whole-frame wall clock")

        # The stacked common-canvas dense pass (the kernel's jnp mirror):
        # quantifies the ragged-padding waste that keeps it out of the
        # production CPU fallback.
        levels = [jax.block_until_ready(lv)
                  for lv in pyramid.build_pyramid_batched(imgs, ocfg)]
        thr = float(ocfg.fast_threshold)
        t_loop, _ = _bench(
            jax.jit(lambda ls: [ops.fast_blur_nms_batched(
                lv, thr, impl="ref") for lv in ls]), levels, iters=iters)
        t_stack, _ = _bench(
            jax.jit(lambda ls: ops.fast_blur_nms_pyramid_stacked_jnp(
                ls, thr)), levels, iters=iters)
        emit("whole_frame", f"dense_stacked_overhead_{res}",
             round(t_stack / t_loop, 2), "x",
             "stacked common-canvas pass / per-level loop (jnp dense "
             "stage; padding waste)")

        # Launch counts: trace-only (no kernel execution) under Pallas.
        with ops.launch_audit() as audit:
            jax.eval_shape(lambda im: extract_features_per_level(
                im, ocfg, impl="pallas"), imgs)
        n_per = audit.count
        with ops.launch_audit() as audit:
            jax.eval_shape(lambda im: orb.extract_features_batched(
                im, ocfg, impl="pallas"), imgs)
        n_whole = audit.count
        emit("whole_frame", f"launches_per_level_{res}", n_per, "kernels",
             "2 per pyramid level")
        emit("whole_frame", f"launches_whole_frame_{res}", n_whole,
             "kernels", "2 per frame")

    # Launch-count regression gate rows: the CI step
    # (benchmarks.check_launches) fails when actual > budget.
    h, w = (240, 320) if quick else (480, 640)
    gcfg = ORBConfig(height=h, width=w, n_levels=2, max_features=512,
                     max_disparity=64)
    intr = CameraIntrinsics(cx=w / 2.0, cy=h / 2.0)
    gimgs = jnp.zeros((4, h, w), jnp.float32)
    gvs = VisualSystem(RigConfig.quad(intr), PipelineConfig(orb=gcfg))
    actual = gvs.traced_launches("process_frame", gimgs)
    budget = 3
    emit("launch_gate", "quad_frame_launches", actual, "kernels",
         f"traced, 4 cams {w}x{h} x {gcfg.n_levels} levels")
    emit("launch_gate", "quad_frame_budget", budget, "kernels",
         "whole-frame FE (1 dense + 1 sparse) + 1 fused FM")
    emit("launch_gate", "quad_frame_input_bytes", 4 * h * w * 4, "bytes",
         f"4 f32 camera slabs {w}x{h}; /4 under precision='uint8'")


def table_fm_fused_vs_unfused(quick=False):
    """Tentpole regression number for the FM stage: the fused megakernel
    (ONE launch per frame — masked Hamming running-argmin + in-kernel
    11x11/strip patch reads + SAD sweep, stereo pairs folded into the
    grid) vs the unfused schedule (``hamming_match`` kernel + host-graph
    full-image pad + 2*K ``dynamic_slice`` gathers per pair, twice, +
    ``sad_search`` kernel, vmapped over pairs).

    Wall clock is measured on the jnp paths (interpret-free CPU);
    launch counts are traced under the Pallas impl — the deterministic,
    machine-independent half, gated in CI via the launch_gate rows.
    """
    from repro.core import extract_features_batched, match_pair_fused
    from repro.core import match_pair_unfused
    from repro.core.frontend import _split_cameras
    resolutions = [(480, 640)] + ([] if quick else [(720, 1280)])
    for h, w in resolutions:
        rng = np.random.RandomState(7)
        imgs = jnp.asarray(rng.randint(0, 256, (4, h, w))
                           .astype(np.float32))
        ocfg = ORBConfig(height=h, width=w, n_levels=2,
                         max_features=1000, max_disparity=96)
        intr = CameraIntrinsics(cx=w / 2.0, cy=h / 2.0)
        res = f"{w}x{h}"
        # FE once, outside the timed region: both contenders consume
        # identical (images, features) inputs.
        feats = jax.block_until_ready(
            extract_features_batched(imgs, ocfg, impl="ref"))
        feat_l, feat_r = _split_cameras(feats, n_pairs=2)
        pairs = imgs.reshape(2, 2, h, w)

        def fm_fused(p, fl, fr, impl="ref"):
            return match_pair_fused(p[:, 0], p[:, 1], fl, fr, ocfg,
                                    intr, impl=impl)

        def fm_unfused(p, fl, fr, impl="ref"):
            return jax.vmap(
                lambda pp, l_, r_: match_pair_unfused(
                    pp[0], pp[1], l_, r_, ocfg, intr, impl=impl)
            )(p, fl, fr)

        iters = 4 if (h, w) == (720, 1280) else 10
        t_unf = _bench_median(jax.jit(fm_unfused), pairs, feat_l, feat_r,
                              iters=iters)
        t_fus = _bench_median(jax.jit(fm_fused), pairs, feat_l, feat_r,
                              iters=iters)
        emit("fm_fused", f"unfused_ms_{res}", round(t_unf * 1e3, 2),
             "ms", "2 pairs, hamming + gather chain + sad (jnp)")
        emit("fm_fused", f"fused_ms_{res}", round(t_fus * 1e3, 2),
             "ms", "2 pairs, one fused FM dispatch (jnp)")
        emit("fm_fused", f"speedup_{res}", round(t_unf / t_fus, 2), "x",
             "unfused / fused wall clock")

        # Launch counts: trace-only (no kernel execution) under Pallas.
        with ops.launch_audit() as audit:
            jax.eval_shape(lambda p, fl, fr: fm_unfused(p, fl, fr, "pallas"),
                           pairs, feat_l, feat_r)
        n_unf = audit.count
        with ops.launch_audit() as audit:
            jax.eval_shape(lambda p, fl, fr: fm_fused(p, fl, fr, "pallas"),
                           pairs, feat_l, feat_r)
        n_fus = audit.count
        emit("fm_fused", f"launches_unfused_{res}", n_unf, "kernels",
             "hamming + sad per traced pair vmap (+ host-graph gathers)")
        emit("fm_fused", f"launches_fused_{res}", n_fus, "kernels",
             "1 megakernel launch, pair axis in the grid")
    # FM launch gate: one fused launch per frame for both pairs.
    emit("launch_gate", "fm_frame_launches", n_fus, "kernels",
         "traced fused FM, 2 stereo pairs")
    emit("launch_gate", "fm_frame_budget", 1, "kernels",
         "single FM megakernel launch per frame")


def table_fleet(quick=False):
    """Fleet batching (PR 5, the `VisualSystem` session API): an N-rig
    fleet frame folds the leading rig axis into the camera/pair batch
    axes of the already-batched kernels, so the WHOLE fleet frame costs
    the same 3 traced launches as one rig (1 dense FE + 1 sparse FE +
    1 fused FM) — the deterministic half, gated in CI via the
    ``launch_gate/fleet_frame_*`` rows.  Wall clock compares the fleet
    dispatch against the per-rig python loop on the jnp path.
    """
    h, w = (240, 320) if quick else (480, 640)
    n_rigs = 4
    ocfg = ORBConfig(height=h, width=w, n_levels=2, max_features=512,
                     max_disparity=64)
    intr = CameraIntrinsics(cx=w / 2.0, cy=h / 2.0)
    rng = np.random.RandomState(7)
    fleet = jnp.asarray(
        rng.randint(0, 256, (n_rigs, 4, h, w)).astype(np.float32))
    vs = VisualSystem(RigConfig.quad(intr), PipelineConfig(orb=ocfg))
    res = f"{w}x{h}"

    iters = 3 if (h, w) == (480, 640) else 5
    t_loop, _ = _bench(
        lambda f: [vs.process_frame(f[r]) for r in range(n_rigs)],
        fleet, iters=iters)
    t_fleet, _ = _bench(vs.process_fleet, fleet, iters=iters)
    emit("fleet", f"per_rig_loop_ms_{res}", round(t_loop * 1e3, 2), "ms",
         f"{n_rigs} rigs x 3 dispatches each (jnp)")
    emit("fleet", f"fleet_ms_{res}", round(t_fleet * 1e3, 2), "ms",
         f"{n_rigs} rigs, one 3-dispatch fleet frame (jnp)")
    emit("fleet", f"speedup_{res}", round(t_loop / t_fleet, 2), "x",
         "per-rig loop / fleet wall clock")

    # Launch gate: trace-only (no kernel execution) under Pallas.
    actual = vs.traced_launches("process_fleet", fleet)
    emit("launch_gate", "fleet_frame_launches", actual, "kernels",
         f"traced, {n_rigs} rigs x 4 cams {res} x {ocfg.n_levels} levels")
    emit("launch_gate", "fleet_frame_budget", 3, "kernels",
         "rig axis folded into the batched kernels: fleet == single-rig "
         "budget")
    emit("launch_gate", "fleet_frame_input_bytes", n_rigs * 4 * h * w * 4,
         "bytes", f"{n_rigs} rigs x 4 f32 camera slabs {res}; /4 under "
         "precision='uint8'")


def table_service(quick=False):
    """Streaming fleet service (PR 6, `repro.serving`): sustained
    frames/sec at N rigs through the full submit -> bucketed batch ->
    masked `process_fleet` -> supervise loop, under synthetic arrival
    jitter and a ~10% injected fault rate (dead camera / corrupt frame
    / trigger desync) — the robustness tax measured, not assumed.  Also
    emits the `launch_gate/degraded_fleet_frame_*` rows CI enforces: a
    fleet frame with dead cameras masked out still traces EXACTLY 3
    launches (masking is elementwise jnp, not a kernel)."""
    from repro.serving import (FaultInjector, FaultSpec, FleetService,
                               QueueConfig, SupervisorConfig, run_episode)
    h, w = (48, 64) if quick else (120, 160)
    n_rigs, t_total = 4, 6
    dt = 1.0 / 30.0
    scfg = scenes.SceneConfig(height=h, width=w, n_points=60, seed=11,
                              baseline=0.3)
    fleet, intr, _ = scenes.render_fleet_sequence(scfg, t_total, n_rigs)
    fleet = jax.block_until_ready(fleet)
    ocfg = ORBConfig(height=h, width=w, n_levels=2, max_features=64,
                     max_disparity=32)
    rig = RigConfig.quad(intr, desync_policy="degrade", max_desync=1e-3)

    def specs():
        # ~10% of the n_rigs * t_total frame slots carry a fault,
        # deterministic slots, kinds round-robin; every rig jitters.
        slots = [(r, t) for r in range(n_rigs) for t in range(t_total)]
        n_faults = max(1, round(0.1 * len(slots)))
        idx = np.random.RandomState(0).choice(len(slots), n_faults,
                                              replace=False)
        kinds = ("dead_camera", "corrupt_frame", "desync")
        out = [FaultSpec(kinds[i % 3], rig=slots[j][0], start=slots[j][1],
                         stop=slots[j][1] + 1, camera=slots[j][0] % 4,
                         magnitude=1.0)
               for i, j in enumerate(sorted(idx))]
        out += [FaultSpec("arrival_jitter", rig=r, magnitude=0.3 * dt)
                for r in range(n_rigs)]
        return out

    def episode(vs):
        svc = FleetService(
            vs, QueueConfig(bucket_sizes=(1, 2, 4), deadline_s=dt),
            SupervisorConfig(heartbeat_timeout_s=3 * dt,
                             backoff_base_s=dt, backoff_max_s=4 * dt))
        return run_episode(svc, fleet, dt=dt,
                           injector=FaultInjector(specs(), seed=0))

    vs = VisualSystem(rig, PipelineConfig(orb=ocfg))
    episode(vs)                       # warmup: trace the bucket shapes
    t0 = time.perf_counter()
    result = episode(vs)
    wall = time.perf_counter() - t0
    served = result.status["counters"]["frames_out"]
    degraded = sum(r.status == "degraded" for r in result.reports)
    res = f"{w}x{h}"
    emit("service", f"sustained_fps_{n_rigs}rigs_{res}",
         round(served / wall, 1), "fps",
         f"{served} frames served in {wall*1e3:.0f}ms, ~10% fault rate "
         "+ arrival jitter")
    emit("service", "frames_degraded", degraded, "frames",
         "dead camera / corrupt slab / desync -> surviving pairs")
    emit("service", "frames_dropped",
         result.status["counters"]["frames_in"] - served, "frames",
         "all-dead or desync-dropped intake")
    emit("service", "batches", result.status["counters"]["batches"],
         "dispatches", "bucketed fleet batches (3 launches each)")

    # Degraded-fleet launch gate: dead cameras must not add launches.
    mask = np.ones((n_rigs, 4), dtype=bool)
    mask[0, 3] = False
    mask[2, 0] = False
    actual = vs.traced_launches("process_fleet", fleet[0],
                                jnp.asarray(mask))
    emit("launch_gate", "degraded_fleet_frame_launches", actual, "kernels",
         f"traced, {n_rigs} rigs with 2 dead cameras masked, {res}")
    emit("launch_gate", "degraded_fleet_frame_budget", 3, "kernels",
         "degradation is elementwise masking — same 3-launch schedule")


def table_precision(quick=False):
    """Low-precision integer datapath (this PR): the whole image path —
    pyramid slabs, fused blur accumulation, FAST scores, patch moments,
    descriptor selection, FM slab reads — runs in integers when the
    session is built with ``PipelineConfig(precision='uint8')``.

    Measures f32 vs uint8 ``process_frame`` wall clock on the jnp path,
    and COMPUTES the resident-slab bytes/pair of the fused FM launch
    from the actual padded slab shapes (``ops._pad_fm_slab`` via
    ``jax.eval_shape`` — no allocation): the uint8 path holds the SAME
    padded geometry in 1-byte elements, a 4x VMEM cut (the acceptance
    floor is 3.5x), in the same 3-launch budget — gated in CI via the
    ``launch_gate/u8_*`` rows emitted here.
    """
    rng = np.random.RandomState(13)
    resolutions = [(480, 640)] + ([] if quick else [(720, 1280)])
    for h, w in resolutions:
        res = f"{w}x{h}"
        ocfg = ORBConfig(height=h, width=w, n_levels=2, max_features=512,
                         max_disparity=64)
        intr = CameraIntrinsics(cx=w / 2.0, cy=h / 2.0)
        rig = RigConfig.quad(intr)
        imgs_u8 = rng.randint(0, 256, (4, h, w)).astype(np.uint8)
        vs_f = VisualSystem(rig, PipelineConfig(orb=ocfg, impl="ref"))
        vs_u = VisualSystem(rig, PipelineConfig(orb=ocfg, impl="ref",
                                                precision="uint8"))
        iters = 3 if (h, w) == (720, 1280) else 5
        t_f = _bench_median(vs_f.process_frame,
                            jnp.asarray(imgs_u8.astype(np.float32)),
                            iters=iters)
        t_u = _bench_median(vs_u.process_frame, jnp.asarray(imgs_u8),
                            iters=iters)
        emit("precision", f"f32_frame_ms_{res}", round(t_f * 1e3, 2),
             "ms", "quad frame, f32 slabs (jnp path)")
        emit("precision", f"u8_frame_ms_{res}", round(t_u * 1e3, 2),
             "ms", "quad frame, uint8 slabs / int32 accumulators (jnp "
             "path)")
        emit("precision", f"u8_speedup_{res}", round(t_f / t_u, 2), "x",
             "f32 / uint8 wall clock (host jnp; the VMEM/bandwidth win "
             "is the computed rows below)")

        # Resident-slab bytes of the fused FM launch, computed from the
        # ACTUAL padded shapes the dispatch builds (padding geometry is
        # dtype-independent, so the ratio is exactly itemsize).
        ry = ocfg.sad_window // 2
        def _slab_bytes(dtype):
            one = jax.ShapeDtypeStruct((1, h, w), dtype)
            sl = jax.eval_shape(lambda x: ops._pad_fm_slab(x, ry, ry),
                                one)
            sr = jax.eval_shape(
                lambda x: ops._pad_fm_slab(x, ry, ry + ocfg.sad_range),
                one)
            return int((np.prod(sl.shape) + np.prod(sr.shape))
                       * np.dtype(dtype).itemsize)
        b_f, b_u = _slab_bytes(jnp.float32), _slab_bytes(jnp.uint8)
        emit("precision", f"f32_fm_slab_bytes_per_pair_{res}", b_f,
             "bytes", "padded level-0 L+R slabs resident in the FM "
             "megakernel")
        emit("precision", f"u8_fm_slab_bytes_per_pair_{res}", b_u,
             "bytes", "same padded geometry, 1-byte elements")
        emit("precision", f"u8_slab_reduction_{res}",
             round(b_f / b_u, 2), "x",
             "resident FM slab bytes f32 / uint8 (acceptance floor "
             "3.5x)")

    # Launch-count regression gates: the uint8 schedule is the SAME
    # 3 launches (1 dense FE + 1 sparse FE + 1 fused FM) per frame and
    # per N-rig fleet frame — dtype switches the kernels' element type,
    # not the launch graph.
    h, w = (240, 320) if quick else (480, 640)
    gcfg = ORBConfig(height=h, width=w, n_levels=2, max_features=512,
                     max_disparity=64)
    gvs = VisualSystem(RigConfig.quad(CameraIntrinsics(cx=w / 2.0,
                                                       cy=h / 2.0)),
                       PipelineConfig(orb=gcfg, precision="uint8"))
    gimgs = jnp.zeros((4, h, w), jnp.uint8)
    actual = gvs.traced_launches("process_frame", gimgs)
    emit("launch_gate", "u8_frame_launches", actual, "kernels",
         f"traced, uint8 datapath, 4 cams {w}x{h} x {gcfg.n_levels} "
         "levels")
    emit("launch_gate", "u8_frame_budget", 3, "kernels",
         "uint8 quad frame: same 3-launch schedule as f32")
    n_rigs = 4
    fleet = jnp.zeros((n_rigs, 4, h, w), jnp.uint8)
    actual = gvs.traced_launches("process_fleet", fleet)
    emit("launch_gate", "u8_fleet_frame_launches", actual, "kernels",
         f"traced, uint8 datapath, {n_rigs} rigs x 4 cams {w}x{h}")
    emit("launch_gate", "u8_fleet_frame_budget", 3, "kernels",
         "uint8 fleet frame: same 3-launch schedule as f32")


def table_localization(quick=False):
    """Localization backend (this PR): disparity -> depth -> rig-frame
    points, the one-launch temporal matcher, and the batched robust
    Procrustes solve, closed against ``data.scenes`` ground truth.

    Emits the ``accuracy_gate/*`` rows CI enforces: ATE / RPE of a
    localized ``run`` over a constant-twist scene must stay under
    pinned limits (~2x the measured baseline) for BOTH the f32 and the
    uint8 integer datapath — so neither a solver regression nor a
    quantization change can silently walk the trajectory error up.
    Also emits the ``launch_gate/loc_*`` rows: a localized frame (and
    fleet frame) costs at most 3 frontend + 1 backend launches."""
    from repro import localization as loc
    h, w = (96, 128) if quick else (160, 240)
    kmax = 96 if quick else 128
    t_total = 4 if quick else 6
    scfg = scenes.SceneConfig(height=h, width=w, baseline=0.5, seed=1)
    seq = scenes.render_sequence(scfg, t_total, step_t=(0.25, 0.0, 0.1),
                                 yaw_per_frame=0.0)
    frames = jax.block_until_ready(jnp.asarray(seq.frames))
    ocfg = ORBConfig(height=h, width=w, max_features=kmax,
                     fast_threshold=15)
    res = f"{w}x{h}"

    def gate(tag, vs, fr):
        t_wall, out = _bench(vs.run, fr, iters=3, warmup=1)
        m = loc.trajectory_metrics(out.pose.rotation,
                                   out.pose.translation, seq.poses)
        inl = np.asarray(out.pose.inliers)
        emit("localization", f"run_ms_{tag}_{res}", round(t_wall * 1e3, 1),
             "ms", f"{t_total}-frame localized run "
             "(3 launches/step + 1 temporal)")
        emit("localization", f"mean_inliers_{tag}",
             round(float(inl[1:].mean()), 1), "points",
             "per-transition robust-solve support")
        emit("localization", f"travel_{tag}", round(m["travel_m"], 3),
             "m", "ground-truth path length")
        for key, (metric, limit, unit) in loc.ACCURACY_LIMITS.items():
            emit("accuracy_gate", f"{key}_{tag}", round(m[metric], 4),
                 unit, f"{t_total}-frame constant-twist scene {res} "
                 "vs ground truth")
            emit("accuracy_gate", f"{key}_{tag}_limit", limit,
                 unit, "pinned ~2x the measured baseline")
        return out

    rig = RigConfig.quad(seq.intrinsics)
    vs = VisualSystem(rig, PipelineConfig(orb=ocfg, localize=True))
    gate("f32", vs, frames)
    u8 = jnp.asarray(np.round(np.clip(np.asarray(frames), 0.0, 255.0))
                     .astype(np.uint8))
    vs_u8 = VisualSystem(rig, PipelineConfig(orb=ocfg, localize=True,
                                             precision="uint8"))
    gate("u8", vs_u8, u8)

    im = frames[0]
    actual = vs.traced_launches("process_frame", im)
    emit("launch_gate", "loc_frame_launches", actual, "kernels",
         f"traced localized quad frame {res}: 3 frontend + 1 temporal")
    emit("launch_gate", "loc_frame_budget", 4, "kernels",
         "frame budget with the localization backend folded in")
    actual = vs.traced_launches("process_fleet", jnp.stack([im, im]))
    emit("launch_gate", "loc_fleet_frame_launches", actual, "kernels",
         "traced localized 2-rig fleet frame: the rig axis folds into "
         "the one temporal launch")
    emit("launch_gate", "loc_fleet_frame_budget", 4, "kernels",
         "fleet == single-rig localized budget")


def table_failover(quick=False):
    """Multi-host failover (PR 9, `repro.serving.failover` +
    `repro.serving.snapshot`): two measured episodes on the SAME
    `run_episode` driver the fault-injection tests use.

    Episode A — host_down + faulted dispatch: one of two host fault
    domains dies mid-stream and its rigs are redistributed over the
    survivor while a `dispatch_error` window exercises the guard's
    seeded retry.  Reports frames dropped (0 is the claim: elastic
    redistribution keeps every queued frame servable), rigs moved, and
    dispatch retries.

    Episode B — kill-and-recover: the service object is destroyed after
    its crash frame and rebuilt cold from the newest crash-consistent
    snapshot; reports the restore wall clock and the on-disk snapshot
    footprint.

    Also emits the `launch_gate/restored_fleet_frame_*` rows CI
    enforces: a fleet frame dispatched by a RESTORED service traces the
    same 3 launches — recovery repopulates state, it never widens the
    launch graph."""
    import shutil
    import tempfile

    from repro.serving import (DispatchGuard, DispatchGuardConfig,
                               FaultInjector, FaultSpec, FleetService,
                               HostMap, QueueConfig, SupervisorConfig,
                               run_episode, snapshot)
    h, w = (48, 64) if quick else (96, 128)
    n_rigs, t_total = 4, 6
    dt = 1.0 / 30.0
    scfg = scenes.SceneConfig(height=h, width=w, n_points=60, seed=11,
                              baseline=0.3)
    fleet, intr, _ = scenes.render_fleet_sequence(scfg, t_total, n_rigs)
    fleet = jax.block_until_ready(fleet)
    ocfg = ORBConfig(height=h, width=w, n_levels=2, max_features=64,
                     max_disparity=32)
    vs = VisualSystem(RigConfig.quad(intr), PipelineConfig(orb=ocfg))
    res = f"{w}x{h}"

    def service():
        return FleetService(
            vs, QueueConfig(bucket_sizes=(1, 2, 4), deadline_s=dt),
            SupervisorConfig(heartbeat_timeout_s=3 * dt,
                             backoff_base_s=dt, backoff_max_s=4 * dt),
            guard=DispatchGuard(DispatchGuardConfig(
                backoff_base_s=dt, backoff_max_s=4 * dt)),
            host_map=HostMap(["host0", "host1"]))

    # Episode A: host0 dies at frame 2; one dispatch window faults.
    inj = FaultInjector([
        FaultSpec("host_down", rig="host0", start=2),
        FaultSpec("dispatch_error", start=1, stop=2, magnitude=1),
    ], seed=0)
    resa = run_episode(service(), fleet, dt=dt, injector=inj)
    c = resa.status["counters"]
    emit("failover", "frames_dropped_host_down",
         c["frames_in"] - c["frames_out"], "frames",
         f"{n_rigs} rigs {res}, host0 of 2 lost at frame 2 — elastic "
         "redistribution keeps queued frames servable")
    emit("failover", "rigs_redistributed", c["rigs_redistributed"],
         "rigs", "moved to the surviving domain (pose chains gapped)")
    emit("failover", "dispatch_retries", c.get("dispatch_retries", 0),
         "retries", "guarded dispatch recovered the injected error "
         f"({c.get('dropped_dispatch', 0)} batches dropped)")

    # Episode B: crash after frame 2, rebuild cold, restore newest
    # verifiable snapshot.
    ckpt = tempfile.mkdtemp(prefix="repro-failover-bench-")
    try:
        resb = run_episode(service(), fleet, dt=dt, snapshot_dir=ckpt,
                           crash_at=2, restore=service)
        rec = resb.recovery
        emit("failover", "recovery_ms",
             round(rec["recovery_wall_s"] * 1e3, 2), "ms",
             "cold FleetService rebuild + snapshot verify/restore "
             f"(restored step {rec['restored_step']})")
        import os
        newest = sorted(d for d in os.listdir(ckpt)
                        if d.startswith("step_")
                        and not d.endswith(".tmp"))[-1]
        sdir = os.path.join(ckpt, newest)
        nbytes = sum(os.path.getsize(os.path.join(sdir, f))
                     for f in os.listdir(sdir))
        emit("failover", "snapshot_bytes", nbytes, "bytes",
             f"one crash-consistent step dir: supervisor ledger + "
             f"pose states + pending frames, {n_rigs} rigs {res}")

        # Launch gate: restore into a fresh service, then trace a fleet
        # frame — recovery must not widen the 3-launch schedule.
        svc2 = service()
        snapshot.restore(svc2, ckpt)
        actual = svc2.vs.traced_launches("process_fleet",
                                         jnp.asarray(fleet[0]))
        emit("launch_gate", "restored_fleet_frame_launches", actual,
             "kernels",
             f"traced fleet frame on a snapshot-restored service, "
             f"{n_rigs} rigs {res}")
        emit("launch_gate", "restored_fleet_frame_budget", 3, "kernels",
             "restore repopulates state, never the launch graph")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_frontend.json",
                    help="JSON artifact path ('' to disable)")
    args = ap.parse_args()
    enable_compile_cache()
    print("table,name,value,unit,note")
    t0 = time.time()
    table1_latency_split(args.quick)
    table_fe_fm_ratio(args.quick)
    table2_module_cost(args.quick)
    table3_accuracy(args.quick)
    table4_throughput(args.quick)
    table_fused_vs_seed(args.quick)
    table_describe_fused_vs_gather(args.quick)
    table_whole_frame_vs_per_level(args.quick)
    table_fm_fused_vs_unfused(args.quick)
    table_fleet(args.quick)
    table_service(args.quick)
    table_precision(args.quick)
    table_localization(args.quick)
    table_failover(args.quick)
    print(f"# done in {time.time() - t0:.1f}s ({len(ROWS)} rows)")
    if args.out:
        rows = [{"table": t, "name": n, "value": v, "unit": u, "note": note}
                for t, n, v, u, note in ROWS]
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "quick": bool(args.quick)}, f, indent=1)
        print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
