"""A whole run of the stream cell on the CPU at a tiny frame: correct on
the sound program, not correct with the control in its place or with a
fault planted in the timed path; and the command's refusal without a
chip."""

from __future__ import annotations

import pytest

from chip_bench_tiny import CONTROL, faulty, run

CELL = "quad720.stream"


def test_sound_run_matches_the_reference():
    # The trajectory limits are the 720p configuration's; at this tiny
    # frame they are only reported, and the chip runs hold them.
    res = run(CELL)
    checks = res["checks"]
    assert checks["mismatched_elements"]["value"] == 0, checks
    assert checks["unanswered"]["value"] == 0
    assert checks["lap_frames_missing"]["value"] == 0
    assert {"ate_m", "rpe_t_m", "rpe_r_deg"} <= set(checks)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window"]["retraces"] == 0
    assert res["window"]["compile_events"] == 0
    assert res["window"]["frames"] == res["attempted"]
    assert res["window"]["slowest_frame_at_s"] >= 0
    assert res["metrics"] == {}           # no number from a CPU run


@pytest.mark.parametrize("fault", ["answer", "state"])
def test_planted_fault_is_not_correct(fault):
    res = run(CELL, faulty(fault))
    assert not res["correct"]
    assert res["checks"]["mismatched_elements"]["value"] > 0


def test_control_is_not_correct():
    res = run(CELL, CONTROL)
    assert not res["correct"]
    assert res["checks"]["mismatched_elements"]["value"] > 0


def test_command_refuses_without_a_chip(capsys):
    from benchmarks.chip import run as command
    rc = command.main(["--workload", CELL, "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err
