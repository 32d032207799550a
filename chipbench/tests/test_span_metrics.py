"""The readers of the reservation scan, backfill, geometry ranking and the
backend's fetch, on a hand-made span list (Chrome trace events of the
``repro.obs`` tracer: ``ts`` and ``dur`` in microseconds), and ``None``
where the span they read is missing, as in a program without it.

    python -m pytest -q chipbench/tests
"""

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def span(name, ts, dur, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1, "tid": tid, "args": args}


# One blocked step (0..1000 us) and one plain arrival (2000..2600 us), 4 events.
SPANS = [
    span("scheduler.step", 0, 1000, events=2),
    span("scheduler.place", 10, 300, job=1, units=8),
    span("allocation.rank", 20, 20, units=8),
    span("backend.contention_field", 100, 200, phase="execute"),
    span("xla.call", 105, 50),
    span("xla.fetch", 160, 130),
    # the blocked head's own ranking, outside placement
    span("allocation.rank", 400, 30, units=8),
    span("scheduler.reserve", 450, 350, job=1, units=8, probes=7),
    # two backfill tries; the third place span is on another thread
    span("scheduler.backfill", 820, 150),
    span("scheduler.place", 830, 40, job=3, units=2),
    span("allocation.rank", 835, 10, units=2),
    span("scheduler.place", 880, 60, job=4, units=1),
    span("allocation.rank", 885, 10, units=1),
    span("scheduler.place", 900, 20, tid=2, job=5, units=1),
    span("scheduler.step", 2000, 600, events=1),
    span("scheduler.place", 2010, 500, job=2, units=1),
    span("allocation.rank", 2020, 10, units=1),
    span("backend.contention_field", 2050, 400, phase="compile"),
    span("xla.call", 2055, 300),
    span("xla.fetch", 2360, 80),
]
UNITS = [{"log": [None] * 4}]


@pytest.mark.parametrize("name,value", [
    ("reserve_ms_per_event", 0.350 / 4),
    ("reserve_probes_per_event", 7 / 4),
    ("backfill_tries_per_event", 2 / 4),
    # the blocked head's ranking is left out
    ("rank_ms_per_event", (20 + 10 + 10 + 10) * 1e-3 / 4),
    # the fetch of the compile-phase dispatch is left out
    ("backend_fetch_ms_per_event", 0.130 / 4),
])
def test_reader_value(name, value):
    assert reader(name)({"units": UNITS, "spans": SPANS}) == pytest.approx(value)


@pytest.mark.parametrize("name,missing", [
    ("reserve_ms_per_event", "scheduler.reserve"),
    ("reserve_probes_per_event", "scheduler.reserve"),
    ("backfill_tries_per_event", "scheduler.backfill"),
    ("rank_ms_per_event", "allocation.rank"),
    ("backend_fetch_ms_per_event", "xla.fetch"),
])
def test_reader_finds_nothing(name, missing):
    spans = [s for s in SPANS if s["name"] != missing]
    assert reader(name)({"units": UNITS, "spans": spans}) is None


def test_parts_within_their_wholes():
    ctx = {"units": UNITS, "spans": SPANS}
    assert reader("reserve_ms_per_event")(ctx) <= reader("scheduler_ms_per_event")(ctx)
    assert reader("backend_fetch_ms_per_event")(ctx) <= reader("backend_ms_per_event")(ctx)
    assert reader("rank_ms_per_event")(ctx) <= reader("place_ms_per_event")(ctx)
    # the existing readers read the same with the new spans taken out
    old = [s for s in SPANS if s["name"] not in (
        "scheduler.reserve", "scheduler.backfill", "allocation.rank", "xla.call", "xla.fetch")]
    for name in ("scheduler_ms_per_event", "place_ms_per_event", "backend_ms_per_event"):
        assert reader(name)(ctx) == reader(name)({"units": UNITS, "spans": old})
