"""The trace reduction: pinned on a small trace recorded on the chip,
and on hand-made events whose answer is known."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace  # noqa: E402

MS = 1_000_000          # ns


def _events():
    # Window 0..100 ms.  Two ops overlap (10-30, 20-40), a kernel runs
    # 50-60, a sort 70-75, and one op straddles the window's end.
    ops = [["fusion.1 fusion f32[4]", "fusion", "", 10 * MS, 30 * MS],
           ["fusion.2 fusion f32[4]", "fusion", "", 20 * MS, 40 * MS],
           ["frontend_fused_pyramid_pallas.1 custom-call u8[8,768,1280]",
            "custom-call", "tpu_custom_call", 50 * MS, 60 * MS],
           ["custom-call.2 custom-call u8[4]", "custom-call", "ConcatBitcast",
            60 * MS, 60 * MS],
           ["sort.3 sort s32[4,921600]", "sort", "", 70 * MS, 75 * MS],
           # a loop op, whose event encloses the sort it runs
           ["while.6 while s32[]", "while", "", 70 * MS, 75 * MS],
           ["copy.4 copy f32[4]", "copy", "", 95 * MS, 120 * MS]]
    spans = [["bench.window", 0, 100 * MS],
             ["bench.dispatch", 0, 8 * MS],
             ["bench.fetch", 40 * MS, 50 * MS]]
    return {"ops": {0: ops}, "spans": spans}


def test_busy_union_and_window():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100)
    # 10-40, 50-60, 70-75, 95-100 (clipped) = 30 + 10 + 5 + 5 ms
    assert r["busy_s"] == pytest.approx(0.050)


def test_kernel_sort_and_other_time():
    r = trace.reduce(_events())
    assert trace.seconds(r, lambda n, op: op["kernel"]) == pytest.approx(0.010)
    assert trace.seconds(r, lambda n, op: op["sort"]) == pytest.approx(0.005)
    other = trace.seconds(r, lambda n, op: not op["kernel"])
    assert other == pytest.approx(0.020 + 0.020 + 0.005 + 0.005)
    assert trace.kernel_seconds(r, "dense_fe") == pytest.approx(0.010)
    assert trace.kernel_seconds(r, "fm") == 0.0


def test_idle_gaps_by_host_span():
    r = trace.reduce(_events())
    gaps = dict(r["breakdown"]["idle_gaps"])
    # Gaps: 0-10, 40-50, 60-70, 75-95 ms.  bench.dispatch covers 0-8,
    # bench.fetch covers 40-50; the rest has no span.
    assert gaps["bench.dispatch"] == pytest.approx(0.008)
    assert gaps["bench.fetch"] == pytest.approx(0.010)
    assert gaps["no bench span"] == pytest.approx(0.002 + 0.010 + 0.020)
    top = r["breakdown"]["device_ops"]
    assert top[0][0] in ("fusion.1 fusion f32[4]", "fusion.2 fusion f32[4]")
    assert not any(n.startswith("while") for n, _ in top)
    assert len(top) <= 10


def test_parse_op_names_opcode_and_target():
    text = ('%sort.9 = (s32[4,921600]{1,0:T(4,128)S(1)}, s32[4,921600]{1,0:'
            'T(4,128)}) sort(s32[4,921600]{1,0:T(4,128)S(1)} %fusion.16), '
            'dimensions={1}')
    assert trace.parse_op(text) == ("sort.9 sort s32[4,921600]", "sort", "")
    text = ('%match_fused_pallas.1 = (s32[2,1024,1]{2,1,0:T(8,128)S(1)}) '
            'custom-call(u32[2,1024,8]{2,1,0} %p), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints')
    assert trace.parse_op(text) == (
        "match_fused_pallas.1 custom-call s32[2,1024,1]", "custom-call",
        "tpu_custom_call")
    # an op that takes a custom call's result is not a custom call
    text = ('%fusion.26 = s32[32,640200]{1,0:T(8,128)} fusion(s32[32,6402]'
            ' %custom-call.36), kind=kLoop')
    assert trace.parse_op(text)[1] == "fusion"


def test_no_window_is_an_error():
    ev = _events()
    ev["spans"] = [s for s in ev["spans"] if s[0] != "bench.window"]
    with pytest.raises(ValueError):
        trace.reduce(ev)


@pytest.fixture(scope="module")
def recorded():
    import gzip
    import json
    path = ROOT / "benchmarks" / "chip" / "testdata" / "stream_trace.json.gz"
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    ev["ops"] = {int(k): v for k, v in ev["ops"].items()}
    return ev


def test_recorded_stream_trace(recorded):
    """Six frames of quad720.stream traced on a TPU v5 lite chip."""
    r = trace.reduce(recorded)
    frames = sum(1 for s in recorded["spans"] if s[0] == "bench.dispatch")
    assert frames == 6
    assert r["window_s"] == pytest.approx(0.32124626)
    assert r["busy_s"] == pytest.approx(0.300147202)
    want = {"dense_fe": 0.018969274, "describe": 0.026274334,
            "fm": 0.014366295, "temporal_match": 0.000678894}
    for k, secs in want.items():
        assert trace.kernel_seconds(r, k) == pytest.approx(secs), k
    assert trace.seconds(r, lambda n, op: op["sort"]) == pytest.approx(
        0.225793734)
    assert trace.seconds(r, lambda n, op: not op["kernel"]) == pytest.approx(
        0.239664723)
    assert r["breakdown"]["device_ops"][0][0] == "sort.9 sort s32[4,921600]"
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["bench.fetch"] == pytest.approx(0.019463833, rel=1e-6)
