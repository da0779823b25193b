"""What decides ``correct``: every answer of the window against the
plain reference, and, where the configuration localizes, the trajectory
against the scene's ground truth within the configuration's limits.

Each compared number is returned with its limit.  The limits and the
readings they were set from are in PERF.md.
"""

from __future__ import annotations

import jax

from benchmarks.chip import compare, reference

def reference_answers(config: dict, images, chain: bool) -> dict:
    """Frame index -> the reference's answer for ``images[i]`` (host
    uint8 (n_cameras, H, W)); with ``chain`` each frame is localized
    against the one before it, frame 0 against the empty state."""
    cfg = dict(config["orb"], temporal_radius=config["temporal_radius"])
    rig = dict(config["camera"], **config["rig"])
    frame = jax.jit(lambda im: reference.rig_frame(im, cfg, rig))
    loc = jax.jit(lambda st, pv: reference.localize(st, pv, cfg, rig))
    prev = reference.zero_state(len(rig["pairs"]), cfg["max_features"])
    out = {}
    for i in range(len(images)):
        ans = frame(images[i])
        if chain:
            ans, prev = loc(ans, prev)
        out[i] = compare.to_tree(jax.device_get(ans))
    return out


def check(cell, driver, win, log=print) -> dict:
    config = cell.config
    want = driver.reference()
    localized = bool(config["localize"])
    rules = compare.LOCALIZED if localized else compare.STEREO
    worst, worst_at = 0, {}
    for key, got in win.answers:
        bad = compare.frame_mismatches(compare.to_tree(got), want[key], rules)
        if sum(bad.values()) > worst:
            worst, worst_at = sum(bad.values()), bad
    log(f"compared {len(win.answers)} answers; worst answer's mismatching "
        f"elements by leaf: {worst_at or 0}")
    checks = {
        "mismatched_elements": {"value": worst, "limit": 0},
        "unanswered": {"value": win.attempted - len(win.answers), "limit": 0},
    }
    if localized:
        lap = dict(win.answers[:driver.ring])
        checks["lap_frames_missing"] = {"value": driver.ring - len(lap),
                                        "limit": 0}
        if len(lap) == driver.ring:
            pose = [compare.to_tree(lap[i])["pose"] for i in range(driver.ring)]
            err = reference.trajectory_error(
                [p["rotation"] for p in pose],
                [p["translation"] for p in pose],
                driver.frames.rig_rot, driver.frames.rig_pos)
            for name, limit in config["accuracy_limits"].items():
                checks[name] = {"value": err[name], "limit": limit}
    return checks
