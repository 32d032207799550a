"""The trace reduction on a small recorded trace, against values worked by hand.

The trace below is the shape of a profiler trace of one chip, cut to a
few events: the benchmark's window and annotations on the host plane,
two executions of one jitted program and three of another on the
device's ``XLA Modules`` line, and their operations on ``XLA Ops``.

    python -m pytest -q chipbench/tests
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import reduce as red  # noqa: E402

MS = 1e6  # nanoseconds per millisecond

TRACE = {
    "host": [
        ("chipbench:window", 0 * MS, 100 * MS),
        ("launcher:serve", 5 * MS, 40 * MS),   # 5..45
        ("launcher:serve", 50 * MS, 45 * MS),  # 50..95
        ("scheduler:run", 96 * MS, 2 * MS),    # 96..98
    ],
    "modules": [
        ("jit_decode_step(17)", 10 * MS, 10 * MS),
        ("jit_decode_step(17)", 25 * MS, 10 * MS),
        ("jit_init(3)", 60 * MS, 5 * MS),
        ("jit_init(3)", 70 * MS, 5 * MS),
        ("jit_init(3)", 120 * MS, 5 * MS),  # after the window: left out
    ],
    "ops": [
        ("fusion.1", 10 * MS, 6 * MS),
        ("fusion.2", 14 * MS, 6 * MS),   # overlaps fusion.1: union 10..20
        ("fusion.1", 25 * MS, 10 * MS),  # 25..35
        ("copy.3", 60 * MS, 5 * MS),     # 60..65
        ("copy.3", 70 * MS, 5 * MS),     # 70..75
        ("fusion.2", 97 * MS, 6 * MS),   # 97..103, clipped to 97..100
        ("%while.5 = (s32[], f32[8]) while(%tuple), body=%body", 25 * MS, 10 * MS),  # holds 25..35
    ],
    "devices": [("/device:TPU:0", 0.0, 0.0)],
}


def test_busy_union_and_idle_share():
    out = red.reduce(TRACE)
    # busy: 10..20, 25..35, 60..65, 70..75, 97..100 = 10 + 10 + 5 + 5 + 3 ms
    assert out["busy_s"] == pytest.approx(0.033)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["idle_share"] == pytest.approx(0.67)


def test_device_time_per_program():
    out = red.reduce(TRACE)
    assert red.module_time(out, "jit_decode_step") == (2, pytest.approx(0.020))
    # the execution after the window is not counted
    assert red.module_time(out, "jit_init") == (2, pytest.approx(0.010))
    assert red.module_time(out, "jit_missing") == (0, 0.0)


def test_device_ops_by_time():
    ops = dict(red.reduce(TRACE)["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.016)
    assert ops["fusion.2"] == pytest.approx(0.012)  # its whole duration, as recorded
    assert ops["copy.3"] == pytest.approx(0.010)
    assert "%while.5" not in ops  # the loop holds the others: busy, not ranked


def test_hlo_op_names():
    name = "%fusion.12 = bf16[16,1,2560]{2,0,1} fusion(%p0, %p1), kind=kLoop"
    assert red.op_name(name) == ("%fusion.12", "fusion")
    assert red.op_name("%copy-start.1 = (f32[8], u32[]) copy-start(%x)") == ("%copy-start.1", "copy-start")
    assert red.op_name("fusion.1") == ("fusion.1", "")


def test_gaps_are_labelled_by_annotation():
    gaps = red.reduce(TRACE)["idle_gaps"]
    # idle: 0..10 (10), 20..25 (5), 35..60 (25), 65..70 (5), 75..97 (22)
    assert [round(g, 6) for _, g in gaps] == [0.025, 0.022, 0.010, 0.005, 0.005]
    labels = [n for n, _ in gaps]
    # 35..60: launcher:serve covers 35..45 (10 ms) and 50..60 (10 ms); the tie
    # goes to the shorter annotation, the first call (40 ms)
    assert labels[0] == "launcher:serve"
    # 75..97: the second call covers 75..95, scheduler:run only 96..97
    assert labels[1] == "launcher:serve"
    # 0..10: the first call covers 5..10
    assert labels[2] == "launcher:serve"


def test_gap_outside_any_annotation():
    trace = dict(TRACE, host=[("chipbench:window", 0.0, 10 * MS)], ops=[], modules=[])
    out = red.reduce(trace)
    assert out["idle_share"] == 1.0
    assert out["idle_gaps"] == [["idle", pytest.approx(0.010)]]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        red.reduce(dict(TRACE, host=[]))
