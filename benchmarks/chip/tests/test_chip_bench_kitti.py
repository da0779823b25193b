"""The ``kitti_stereo.stream`` cell on the CPU: it resolves, its own cut
runs through the harness and is correct, the control is not; and the
readers of the frame calls' kernel-grid stats and of the ``canvas``
scope, on hand-made events and on a recorded trace of a program without
them."""

from __future__ import annotations

import copy
import gzip
import json
import sys
import time
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import (harness, program_trace, span_stats,  # noqa: E402
                             trace)
from chip_bench_tiny import CONTROL  # noqa: E402

CELL = "kitti_stereo.stream"
HERE = ROOT / "benchmarks" / "chip"
MS = 1_000_000          # ns
READERS = ("dense_fe_padding_share", "fm_pairs_m", "scope_ms.canvas")


def test_cell_resolves():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == "stream"
    assert cell.config["name"] == "kitti_stereo"
    orb = cell.config["orb"]
    assert (orb["height"], orb["width"], orb["n_levels"],
            orb["max_features"]) == (376, 1241, 8, 2000)
    assert cell.config["rig"]["pairs"] == [[0, 1]]
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "rig_frames_per_s", "frame_ms_p50", "frame_ms_p95", "setup_s"}


def tiny():
    """The cell at 176x583 with 256 features on the jnp path: 8 levels
    down to 49x163, each with an interior past the 16-px border."""
    cell = harness.load_cell(CELL)
    cfg = copy.deepcopy(cell.config)
    s = 176 / cfg["orb"]["height"]
    cfg["orb"].update(height=176, width=583, max_features=256)
    cam = cfg["camera"]
    cam.update(fx=cam["fx"] * s, fy=cam["fy"] * s, cx=cam["cx"] * s,
               cy=cam["cy"] * s)
    cfg["impl"] = "ref"
    return cell._replace(config=cfg)


def _run(factory=None):
    return harness.run_cell(tiny(), 3_000_000_123, 0.5, False,
                            time.perf_counter(), require_tpu=False,
                            session_factory=factory, log=lambda m: None)


def test_sound_run_is_correct():
    res = _run()
    checks = res["checks"]
    assert res["correct"], checks
    assert checks["mismatched_elements"]["value"] == 0
    assert checks["unanswered"]["value"] == 0
    assert checks["lap_frames_missing"]["value"] == 0
    assert {"ate_m", "rpe_t_m", "rpe_r_deg"} <= set(checks)
    assert res["window"]["retraces"] == 0
    assert res["metrics"] == {}           # no number from a CPU run


def test_control_is_not_correct():
    res = _run(CONTROL)
    assert not res["correct"]
    assert res["checks"]["mismatched_elements"]["value"] > 0


# --------------------------------------------------------------------------
# The readers

KITTI = {"fe_tiles": 480, "fe_tiles_real": 230, "fm_pairs": 4_096_000}


def _events(stats=KITTI):
    """Window 0-100 ms; frame calls at 2-5 and 52-56 ms carrying
    ``stats`` beside ``h2d_bytes``, one more at 101-103 ms, outside the
    window.  Device: a canvas pad 4-6 and 57-60 ms, a kernel 6-20 ms."""
    f = "jit(process_frame)"
    ops = [  # display, opcode, target, start, end, scope
        ["fusion.1 fusion u8[16]", "fusion", "", 4, 6,
         f"{f}/dense_fe/canvas/pad"],
        ["frontend_fused_pyramid_pallas.1 custom-call u8[16]",
         "custom-call", "tpu_custom_call", 6, 20,
         f"{f}/dense_fe/pallas_call"],
        ["fusion.2 fusion u8[16]", "fusion", "", 57, 60,
         f"{f}/describe/canvas/concatenate"],
    ]
    ev = {"ops": {0: [[*o[:3], o[3] * MS, o[4] * MS] for o in ops]},
          "op_module": {0: ["jit_process_frame"] * len(ops)}}
    scopes = {"jit_process_frame": {o[0].split(" ")[0]: (o[0], o[5])
                                    for o in ops}}
    frame = dict(stats, h2d_bytes=933_232)
    host = [("bench.window", 0, 100, {}),
            ("repro.process_frame", 1, 9, {"call": 1}),
            ("repro.frame_call", 2, 5, frame),
            ("repro.process_frame", 51, 59, {"call": 2}),
            ("repro.frame_call", 52, 56, frame),
            ("repro.frame_call", 101, 103, frame)]
    ev["host"] = [[n, s * MS, e * MS, "main", st] for n, s, e, st in host]
    ev["spans"] = [h[:3] for h in ev["host"] if h[0].startswith("bench.")]
    return ev, scopes


def _ctx(ev, calls=2):
    return {"reduced": trace.reduce(ev),
            "cell": types.SimpleNamespace(config={}),
            "window": types.SimpleNamespace(calls=calls)}


def _read(name, ctx):
    reader = harness.load_module(HERE / "metrics" / f"{name}.py",
                                 f"test_kitti_metric_{name}")
    return reader.read(ctx)


@pytest.fixture
def run_trace(monkeypatch):
    """Points the readers at hand-made events as if they were the run's
    trace file, and at their scope table as the compiled programs'."""
    def use(ev, scopes):
        program_trace._CACHE.clear()
        span_stats._CACHE.clear()
        monkeypatch.setattr(program_trace, "trace_file", lambda: "run.pb")
        monkeypatch.setattr(program_trace, "events", lambda path: ev)
        monkeypatch.setattr(program_trace, "compiled_scopes",
                            lambda config: scopes)
    yield use
    program_trace._CACHE.clear()
    span_stats._CACHE.clear()


def test_frame_calls_sum_the_window_stats():
    ev, _ = _events()
    got = span_stats.frame_calls(ev)
    assert got["calls"] == 2
    assert got["window_s"] == pytest.approx(0.100)
    assert got["sums"] == {"fe_tiles": 960, "fe_tiles_real": 460,
                           "fm_pairs": 8_192_000, "h2d_bytes": 1_866_464}


@pytest.mark.parametrize("stats,want", [
    (KITTI, {"dense_fe_padding_share": 100 * (1 - 230 / 480),
             "fm_pairs_m": 4.096}),
    ({"fe_tiles": 480, "fe_tiles_real": 420, "fm_pairs": 2_048_000},
     {"dense_fe_padding_share": 12.5, "fm_pairs_m": 2.048}),
], ids=["kitti_stereo", "quad720"])
def test_readers_on_hand_made_events(run_trace, stats, want):
    ev, scopes = _events(stats)
    run_trace(ev, scopes)
    got = {n: _read(n, _ctx(ev)) for n in READERS}
    # canvas: 4-6 and 57-60 ms over two frames
    assert got == pytest.approx(dict(want, **{"scope_ms.canvas": 2.5}))


def test_readers_are_silent_without_the_stats_or_the_scope(run_trace):
    """The parent's program: frame calls carry only ``h2d_bytes`` and no
    op lies under a ``canvas`` scope."""
    ev, scopes = _events({})
    scopes = {m: {n: (d, p.replace("/canvas", "")) for n, (d, p) in t.items()}
              for m, t in scopes.items()}
    run_trace(ev, scopes)
    assert {n: _read(n, _ctx(ev)) for n in READERS} == dict.fromkeys(READERS)


def test_readers_are_silent_on_another_window(run_trace):
    ev, scopes = _events()
    run_trace(ev, scopes)
    other, _ = _events()
    other["spans"][0][2] += MS            # a window 1 ms longer
    assert all(_read(n, _ctx(other)) is None for n in READERS)


def test_readers_are_silent_with_no_trace_file(monkeypatch):
    span_stats._CACHE.clear()
    program_trace._CACHE.clear()
    monkeypatch.setattr(program_trace, "trace_file", lambda: None)
    assert all(_read(n, _ctx(_events()[0])) is None for n in READERS)


def test_readers_are_silent_on_the_recorded_trace(run_trace):
    """Six frames of ``quad720.stream`` traced on a TPU v5 lite chip by
    a program that recorded neither the grid stats nor the scope."""
    with gzip.open(HERE / "testdata" / "stream_program_trace.json.gz",
                   "rt") as f:
        ev = json.load(f)
    for key in ("ops", "op_module", "op_scope"):
        if key in ev:
            ev[key] = {int(k): v for k, v in ev[key].items()}
    scopes = {m: {n: tuple(v) for n, v in t.items()}
              for m, t in ev.pop("scopes").items()}
    run_trace(ev, scopes)
    ctx = _ctx(ev, calls=6)
    assert span_stats.for_ctx(ctx)["calls"] == 6
    assert all(_read(n, ctx) is None for n in READERS)
