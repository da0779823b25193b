#!/usr/bin/env python3
"""Chip smoke run: the paper's quad-camera frame path, end to end, on one
TPU chip, at the paper's full size (``ORBConfig()``: a quad rig at
1280x720, 2 pyramid levels at scale 1.2, 1,000 features,
``max_disparity`` 96, quantized).  Scenes come from ``--seed`` through
``repro.data.scenes``; frames are quantized to integers, as an 8-bit
camera delivers them.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --four-chips    # the sharded fleet, four chips

Phases, in order (one process; every phase must pass):

  (a) one quad frame through ``VisualSystem.process_frame`` with
      ``impl="pallas"``, in ``precision="f32"`` and ``"uint8"``, against
      an ``impl="ref"`` session on the same chip: keypoints,
      descriptors, match indices and depth must be equal, and the
      compiled frame program must hold exactly 3 Mosaic kernels;
  (b) a localized ``VisualSystem.run`` over 6 frames: ATE / RPE within
      ``repro.localization.ACCURACY_LIMITS``, 4 Mosaic kernels;
  (c) a ``FleetService`` episode of 4 quad rigs under a
      ``DispatchGuard``: no dispatch error, stall, retry or drop, every
      rig frame served, each equal to that rig's own ``process_frame``.

``--four-chips`` runs only a ``process_fleet`` of 8 quad rigs sharded
over the four chips (``rig_shard_axis``) against the same fleet
unsharded on chip 0: outputs bit-equal, output sharded over 4 devices.

The timings printed are smoke timings of this run (compilation
included where marked), not benchmark numbers.  The last line of
standard output is the JSON verdict; the script exits non-zero, with no
verdict, when JAX sees no TPU or a phase fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timing(phase: str, what: str, seconds: float) -> None:
    print(f"smoke-timing {phase} {what}: {seconds:.3f} s", flush=True)


def quantized(frames, dtype):
    """Integer-valued frames, as an 8-bit camera delivers them."""
    import numpy as np
    return np.round(np.clip(np.asarray(frames), 0.0, 255.0)).astype(dtype)


def scene(seed: int, n_frames: int, n_rigs: int | None = None):
    """The constant-twist scene of the localization gates, at 720p.
    Baseline 0.2 m keeps the nearest landmarks (2 m) inside
    ``max_disparity`` = 96 px (fx = 921.6 px)."""
    from repro.core import ORBConfig
    from repro.data import scenes
    cfg = ORBConfig()
    scfg = scenes.SceneConfig(height=cfg.height, width=cfg.width,
                              baseline=0.2, seed=seed)
    kw = dict(step_t=(0.25, 0.0, 0.1), yaw_per_frame=0.0)
    if n_rigs is None:
        return scenes.render_sequence(scfg, n_frames, **kw)
    return scenes.render_fleet_sequence(scfg, n_frames, n_rigs, **kw)


def session(intr, impl: str, **pipe):
    from repro.core import ORBConfig, PipelineConfig, RigConfig, VisualSystem
    return VisualSystem(RigConfig.quad(intr),
                        PipelineConfig(orb=ORBConfig(), impl=impl, **pipe))


def mismatches(got, want) -> dict:
    """Leaf path -> number of unequal elements (NaN equals NaN)."""
    import jax
    import numpy as np
    out = {}
    flat_w = dict(jax.tree_util.tree_leaves_with_path(want))
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        g, w = np.asarray(g), np.asarray(flat_w[path])
        if g.shape != w.shape or g.dtype != w.dtype:
            out[jax.tree_util.keystr(path)] = g.size
            continue
        same = g == w
        if g.dtype.kind == "f":
            same |= np.isnan(g) & np.isnan(w)
        bad = int(g.size - np.count_nonzero(same))
        if bad:
            out[jax.tree_util.keystr(path)] = bad
    return out


def kernel_count(tag: str, fn, *args) -> int:
    """Mosaic kernels in the compiled program of ``fn`` — the proof that
    the run used real kernels, neither interpret mode nor the jnp path.
    Prints the compile time of that program."""
    import jax
    t = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    timing(tag, "compile of the program", time.perf_counter() - t)
    return compiled.as_text().count(KERNEL_CALL)


def timed(fn, *args):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def phase_frame(seed: int, impl: str = "pallas") -> None:
    """(a) one quad frame, both datapaths, against the ref sessions."""
    import numpy as np
    seq = scene(seed, 1)
    for precision, dtype in (("f32", np.float32), ("uint8", np.uint8)):
        frame = quantized(seq.frames[0], dtype)
        vs = session(seq.intrinsics, impl, precision=precision)
        vs_ref = session(seq.intrinsics, "ref", precision=precision)
        tag = f"(a) frame {precision}"
        out, t_first = timed(vs.process_frame, frame)
        _, t_steady = timed(vs.process_frame, frame)
        want, _ = timed(vs_ref.process_frame, frame)
        timing(tag, "first call incl. compile", t_first)
        timing(tag, "steady call", t_steady)
        n_feat = int(np.asarray(out.features_l.valid).sum())
        n_match = int(np.asarray(out.matches.valid).sum())
        n_depth = int(np.asarray(out.depth.valid).sum())
        print(f"{tag}: {n_feat} left features, {n_match} matches, "
              f"{n_depth} depths over {vs.rig.n_pairs} pairs", flush=True)
        check(n_feat > 0 and n_match > 0 and n_depth > 0,
              f"{tag}: empty output")
        bad = mismatches(out, want)
        print(f"{tag}: pallas vs ref mismatching elements: {bad or 0}",
              flush=True)
        check(not bad, f"{tag}: pallas differs from ref: {bad}")
        n_k = kernel_count(tag, vs.entry_core("process_frame", impl), frame)
        print(f"{tag}: {n_k} Mosaic kernels in the frame program",
              flush=True)
        check(n_k == 3, f"{tag}: {n_k} Mosaic kernels, want 3")


def phase_localize(seed: int, impl: str = "pallas") -> None:
    """(b) a localized 6-frame run, gated on ATE / RPE."""
    import numpy as np
    from repro import localization
    seq = scene(seed, 6)
    frames = quantized(seq.frames, np.float32)
    vs = session(seq.intrinsics, impl, localize=True)
    out, t_first = timed(vs.run, frames)
    _, t_steady = timed(vs.run, frames)
    timing("(b) localized run", "first call incl. compile", t_first)
    timing("(b) localized run", "steady call (6 frames)", t_steady)
    m = localization.trajectory_metrics(out.pose.rotation,
                                        out.pose.translation, seq.poses)
    valid = np.asarray(out.pose.valid)
    print(f"(b) localized run: valid poses {valid.tolist()}, "
          f"inliers {np.asarray(out.pose.inliers).tolist()}", flush=True)
    for key, (metric, limit, unit) in localization.ACCURACY_LIMITS.items():
        print(f"(b) localized run: {key} {m[metric]:.4f} {unit} "
              f"(limit {limit})", flush=True)
        check(m[metric] <= limit, f"(b) {key} {m[metric]} > {limit}")
    check(bool(valid[1:].all()), "(b) a frame transition has no pose")
    n_k = kernel_count("(b) localized run", vs.entry_core("run", impl),
                       frames)
    print(f"(b) localized run: {n_k} Mosaic kernels in the run program",
          flush=True)
    check(n_k == 4, f"(b) {n_k} Mosaic kernels, want 4")


def phase_fleet(seed: int, impl: str = "pallas", n_frames: int = 3) -> None:
    """(c) a guarded FleetService episode of 4 quad rigs."""
    import numpy as np
    from repro import serving
    n_rigs = 4
    fleet = scene(seed, n_frames, n_rigs)
    frames = quantized(fleet.frames, np.float32)
    vs = session(fleet.intrinsics, impl)
    # Compile the fleet program before the guarded episode, so the
    # guard's watchdog times dispatches, not compilation.
    _, t_compile = timed(vs.process_fleet, frames[0])
    timing("(c) fleet", "first 4-rig call incl. compile", t_compile)
    service = serving.FleetService(
        vs, serving.QueueConfig(bucket_sizes=(n_rigs,)),
        guard=serving.DispatchGuard())
    t = time.perf_counter()
    dt = 1.0 / 30.0
    res = serving.run_episode(service, frames, dt=dt)
    timing("(c) fleet", f"episode ({n_frames} frames x {n_rigs} rigs)",
           time.perf_counter() - t)
    counters = res.status["counters"]
    faults = {k: int(counters.get(k, 0)) for k in (
        "dispatch_errors", "dispatch_stalls", "dispatch_retries",
        "dropped_dispatch")}
    print(f"(c) fleet: {faults}, batches {counters.get('batches', 0)}",
          flush=True)
    check(not any(faults.values()), f"(c) dispatch faults {faults}")
    served = {(r.rig_id, int(round(r.t_arrival / dt))): r
              for r in res.reports if r.status == "ok"}
    want_keys = {(r, t) for r in range(n_rigs) for t in range(n_frames)}
    missing = sorted(want_keys - set(served))
    check(not missing and len(res.reports) == len(want_keys),
          f"(c) unserved rig frames {missing}, "
          f"{len(res.reports)} reports for {len(want_keys)} frames")
    for (r, t), rep in sorted(served.items()):
        bad = mismatches(rep.output, vs.process_frame(frames[t, r]))
        check(not bad, f"(c) rig {r} frame {t} differs from its own "
                       f"process_frame: {bad}")
    print(f"(c) fleet: {len(served)} rig frames served, each equal to "
          "its own process_frame", flush=True)


def phase_four_chips(seed: int, impl: str = "pallas") -> None:
    """8 quad rigs sharded over the four chips vs unsharded on chip 0."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.distributed import sharding
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 chips, "
                             f"JAX sees {len(devices)}")
    fleet = scene(seed, 1, 8)
    frames = quantized(fleet.frames[0], np.float32)     # (8, 4, H, W)
    mesh = Mesh(np.asarray(devices), ("rig",))
    vs_sharded = session(fleet.intrinsics, impl, rig_shard_axis="rig")
    with sharding.use_sharding(mesh, sharding.Rules.make()):
        got, t_sharded = timed(vs_sharded.process_fleet, frames)
        _, t_steady = timed(vs_sharded.process_fleet, frames)
    timing("four-chip", "sharded first call incl. compile", t_sharded)
    timing("four-chip", "sharded steady call (8 rigs)", t_steady)
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(got)}
    print(f"four-chip: output leaves span {sorted(spans)} devices",
          flush=True)
    check(spans == {4}, f"four-chip: output spans {spans} devices, want 4")
    vs_one = session(fleet.intrinsics, impl)
    want, t_one = timed(vs_one.process_fleet,
                        jax.device_put(frames, devices[0]))
    timing("four-chip", "unsharded chip-0 first call incl. compile", t_one)
    bad = mismatches(got, want)
    print(f"four-chip: sharded vs chip-0 mismatching elements: {bad or 0}",
          flush=True)
    check(not bad, f"four-chip: sharded differs from unsharded: {bad}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="scene seed (default 0)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 8-rig fleet sharded over 4 chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro" / "core" / "pipeline.py").is_file():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX sees no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}", flush=True)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_frame, phase_localize, phase_fleet])
    t0 = time.perf_counter()
    try:
        for phase in phases:
            t = time.perf_counter()
            phase(args.seed)
            timing(phase.__name__, "phase wall", time.perf_counter() - t)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    timing("all", "wall", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
