"""The plain reference against the program at sizes a test run holds: on small
Mira streams, light and backlogged, the reference's event log equals the
program's event for event.
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def _mira(load, jobs):
    cfg = json.loads((BENCH / "configs" / "mira.json").read_text())
    mix = json.loads((BENCH / "traffic" / "light.json").read_text())
    mix.update(jobs=jobs, load=load)
    mix["failure_rate"] = 0.03  # a short stream still sees failures and repairs
    return cfg, mix


@pytest.mark.parametrize("load,jobs,seed", [(0.5, 60, 3), (1.2, 80, 2**33 + 5)])
def test_scheduler_reference_log_equals_program(load, jobs, seed):
    from drivers import sched_replay as S

    cfg, mix = _mira(load, jobs)
    stream = S.make_stream(cfg, mix, S.stream_seed(seed, 0))
    assert any(kind == "fail" for _, kind, _ in stream)
    prog = S.program_log(S.replay(cfg, stream)["log"])
    want = S.reference_log(cfg, mix, seed, 0)
    assert len(prog) > 2 * jobs
    assert S.mismatches(prog, want) == 0
