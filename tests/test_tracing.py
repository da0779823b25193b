"""The session's own instrumentation: ``VisualSystem.counters`` and the
``repro.*`` profiler spans of ``process_frame``, read back from a
profiled CPU run at a tiny frame."""

import collections
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CameraIntrinsics, ORBConfig, PipelineConfig,
                        RigConfig, VisualSystem)

H, W = 48, 64
CHILDREN = ("repro.validate", "repro.frame_call", "repro.localize_call")


def _quad(localize=False, **rig_kw):
    ocfg = ORBConfig(height=H, width=W, max_features=16, n_levels=2,
                     max_disparity=24)
    return VisualSystem(
        RigConfig.quad(CameraIntrinsics(cx=W / 2.0, cy=H / 2.0), **rig_kw),
        PipelineConfig(orb=ocfg, localize=localize))


def _frame(seed, *lead):
    rng = np.random.RandomState(seed)
    return rng.randint(0, 256, lead + (4, H, W)).astype(np.float32)


def _spans(tmp_path):
    """Every ``repro.*`` host span of the trace under ``tmp_path``:
    (name, start, end, thread, stats), in start order."""
    from jax.profiler import ProfileData
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, line.name,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _nested(inner, outer):
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def _profiled(tmp_path, call, frames):
    """The ``repro.*`` spans of ``call`` over ``frames``, each call
    compiled before the trace starts."""
    jax.block_until_ready(call(frames[0]))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for frame in frames:
            jax.block_until_ready(call(frame))
    finally:
        jax.profiler.stop_trace()
    return _spans(tmp_path)


@pytest.mark.parametrize("localize", [True, False],
                         ids=["localized", "frontend_only"])
def test_spans_nest_and_carry_the_call_number(tmp_path, localize):
    vs = _quad(localize=localize)
    frame = _frame(0)
    spans = _profiled(tmp_path, vs.process_frame, [frame, frame])
    children = CHILDREN if localize else CHILDREN[:2]
    outer = [s for s in spans if s[0] == "repro.process_frame"]
    assert [int(s[4]["call"]) for s in outer] == [2, 3]
    for name in CHILDREN:
        inner = [s for s in spans if s[0] == name]
        assert len(inner) == (2 if name in children else 0), name
        for s, o in zip(inner, outer):
            assert _nested(s, o), (name, s, o)
    # the children run in order, one after the other
    for o in outer:
        kids = [s for s in spans if s[0] in CHILDREN and _nested(s, o)]
        assert [k[0] for k in kids] == list(children)
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))


def test_counters_count_calls_bytes_and_traces(tmp_path):
    vs = _quad()
    frame = _frame(1)
    device_frame = jnp.asarray(frame)
    spans = _profiled(tmp_path, vs.process_frame,
                      [frame, frame, device_frame])
    assert vs.counters["calls.process_frame"] == 4
    assert vs.counters["traces.process_frame"] == 1
    assert vs.trace_count("process_frame") == 1
    assert vs.trace_count("never_called") == 0
    # each frame call records the host bytes it hands over; a device
    # array is no host transfer
    calls = [s for s in spans if s[0] == "repro.frame_call"]
    assert [int(s[4]["h2d_bytes"]) for s in calls] == [
        frame.nbytes, frame.nbytes, 0]


def test_desync_drop_hands_nothing_over(tmp_path):
    vs = _quad(desync_policy="drop_frame", max_desync=1e-3)
    frame = _frame(2)
    bad = [0.0, 0.0, 0.0, 1.0]
    spans = _profiled(tmp_path,
                      lambda f: vs.process_frame(f, timestamps=bad),
                      [frame])
    assert vs.counters["calls.process_frame"] == 2
    assert vs.trace_count("process_frame") == 0     # never compiled
    # a dropped frame is validated and goes no further
    assert [s[0] for s in spans] == ["repro.process_frame",
                                     "repro.validate"]


def test_programs_are_named_after_their_entry():
    vs = _quad(localize=True)
    frame = _frame(3)
    vs.process_frame(frame)
    names = collections.Counter(
        jit.__wrapped__.__name__ for jit in vs._jitted.values())
    assert names == {"process_frame": 1, "localize_frame": 1}
    text = vs._jitted["process_frame"].lower(frame).as_text()
    assert "jit_process_frame" in text
