"""The control, at a size a test run holds, judged by the comparison that
decides a run's ``correct``: the program reads correct, the control not.

On the chip ``chipbench/control.py`` reads the same numbers at the cell's
own size; those readings set the limits (PERF.md)."""

import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import control  # noqa: E402


def test_scheduler_control_is_not_correct():
    from drivers import sched_replay as S

    cfg = json.loads((BENCH / "configs" / "mira.json").read_text())
    mix = json.loads((BENCH / "traffic" / "light.json").read_text())
    checks = json.loads((BENCH / "checks" / "mira.light.json").read_text())
    mix["jobs"] = 60
    r = {"config": cfg, "mix": mix, "checks": checks}
    state = {"cfg": cfg, "mix": mix}
    row = control.readings(S, r, state, 2**31 + 5, 0.5)
    assert row["program"]["log_mismatch"]["value"] == 0 and row["program_correct"]
    assert row["control"]["log_mismatch"]["value"] > 0 and not row["control_correct"]
