"""A whole run of a cell on the CPU at a small size, past the look for a chip,
and the same run with the timed path broken underneath: ``correct`` must
come out false for each fault the cell can have.  The scheduler's answers
are its event log; the faults it can have are an answer altered where it
is produced (a placement other than the policy's) and a step that leaves
its state unchanged (a completion that frees no midplane).
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 4321  # beyond 32 signed bits, as the driver's seeds are


def sched_cell(trace=False):
    mix = json.loads((BENCH / "traffic" / "light.json").read_text())
    mix.update(jobs=30, failure_rate=0.03)
    return run.run_cell("mira.light", SEED, 0.5, trace, require_tpu=False, cache=False,
                        overrides={"mix": mix, "peaks": PEAKS})


def test_scheduler_run_is_correct():
    out = sched_cell(trace=True)
    assert out["correct"], out["checks"]
    assert out["checks"]["log_mismatch"]["value"] == 0
    assert set(out["metrics"]) >= {"scheduler_ms_per_event", "place_ms_per_event",
                                   "backend_ms_per_event", "device_idle.sched"}


def test_scheduler_placement_altered_is_caught(monkeypatch):
    from repro.network.allocation import MachineState

    monkeypatch.setattr(MachineState, "allocate_scored", MachineState.allocate)
    out = sched_cell()
    assert not out["correct"]
    assert out["checks"]["log_mismatch"]["value"] > 0


def test_scheduler_state_left_unchanged_is_caught(monkeypatch):
    from repro.network.allocation import MachineState

    # a completion that leaves the machine's occupancy as it was
    monkeypatch.setattr(MachineState, "release", lambda self, job_id: None)
    out = sched_cell()
    assert not out["correct"]
    assert out["checks"]["log_mismatch"]["value"] > 0


def test_no_tpu_exits_without_a_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "mira.light", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert "no TPU" in str(e.value.code)
    assert "correct" not in capsys.readouterr().out


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    class Chip:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    with pytest.raises(SystemExit) as e:
        run.find_device(1)
    assert "no row" in str(e.value.code)
