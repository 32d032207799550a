"""Telemetry subsystem (repro.obs): tracer semantics, metrics registry,
non-perturbation of the instrumented engines, Chrome-trace validity, and
the contention-attribution acceptance numbers.

The load-bearing properties pinned here:

* telemetry is **off by default** and its disabled path is a no-op —
  enabling tracing must not change a single scheduler event, netsim
  makespan, or planner table (observe, never perturb);
* the exported Chrome trace is valid JSON whose spans nest properly
  (per thread, intervals are disjoint or contained — never partially
  overlapping) and contains the scheduler / placement / netsim spans;
* ``scheduler_metrics`` is derived purely from the event log + schedule,
  so a replayed service reproduces the metrics snapshot exactly and the
  per-job gauges equal the ``SimulationResult`` fields bit-for-bit;
* contention attribution reproduces the paper's avoidable-contention
  pair on a 16^3 torus: a (8,8,8) placement has no avoidable contention
  while (16,16,2) carries 2x avoidable load (Theorem 3.1-certified).
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

import repro.obs as obs
from repro.obs import TRACER
from repro.obs.contention import attribute_contention, attribute_traffic, render_dashboard
from repro.obs.metrics import MetricsRegistry, scheduler_metrics
from repro.network import IsoperimetricPolicy, MachineState
from repro.network.allocation import ContentionScoredPolicy, JobRequest, simulate_queue
from repro.network.netsim import build_paths, simulate_flows
from repro.network.placement import placement_loads
from repro.network.scheduler import generate_scenario, replay_events, run_scenario


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.clear_telemetry()
    yield
    obs.clear_telemetry()


def _log_key(service):
    return [
        (e.seq, e.time, e.kind, e.job_id, e.cells, e.placement,
         e.priority, e.reason, e.source)
        for e in service.log
    ]


# ---------------------------------------------------------------------------
# Tracer semantics.
# ---------------------------------------------------------------------------
def test_tracer_disabled_by_default():
    assert not TRACER.enabled
    with TRACER.span("x", a=1) as sp:
        sp.annotate(b=2)
        sp.incr("c")
    assert TRACER.events() == []


def test_span_nesting_and_args():
    TRACER.enable(clear=True)
    with TRACER.span("outer", k=1):
        with TRACER.span("inner") as sp:
            sp.annotate(found=True)
    TRACER.disable()
    events = TRACER.events()
    assert [e["name"] for e in sorted(events, key=lambda e: e["ts"])] == [
        "outer", "inner",
    ]
    outer = next(e for e in events if e["name"] == "outer")
    inner = next(e for e in events if e["name"] == "inner")
    assert outer["args"] == {"k": 1}
    assert inner["args"] == {"found": True}
    # containment: inner lies inside outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_timer_measures_even_disabled():
    assert not TRACER.enabled
    with obs.timer("t") as tm:
        sum(range(1000))
    assert tm.elapsed > 0.0
    assert TRACER.events() == []
    TRACER.enable(clear=True)
    with obs.timer("t") as tm:
        pass
    TRACER.disable()
    assert tm.elapsed >= 0.0
    assert [e["name"] for e in TRACER.events()] == ["t"]


def test_tracer_thread_safety():
    TRACER.enable(clear=True)
    barrier = threading.Barrier(4)  # overlap lifetimes so tids are distinct

    def worker(i):
        barrier.wait()
        for j in range(50):
            with TRACER.span("w", i=i, j=j):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    TRACER.disable()
    assert len(TRACER.events()) == 200
    tids = {e["tid"] for e in TRACER.events()}
    assert len(tids) == 4


def _assert_proper_nesting(trace_events):
    """Per tid, spans must be disjoint or nested — no partial overlap."""
    by_tid = {}
    for e in trace_events:
        by_tid.setdefault(e["tid"], []).append(e)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            start, end = e["ts"], e["ts"] + e["dur"]
            while stack and start >= stack[-1]:
                stack.pop()
            if stack:
                assert end <= stack[-1] + 1, (
                    f"span {e['name']} partially overlaps its parent (tid {tid})"
                )
            stack.append(end)


# ---------------------------------------------------------------------------
# Non-perturbation + Chrome trace from a Mira-style replay.
# ---------------------------------------------------------------------------
def test_scheduler_log_identical_with_tracing():
    scenario = generate_scenario((8, 8, 8), 30, seed=5, failure_rate=0.002)
    s_off = run_scenario(scenario, ContentionScoredPolicy())
    TRACER.enable(clear=True)
    s_on = run_scenario(scenario, ContentionScoredPolicy())
    TRACER.disable()
    assert _log_key(s_off) == _log_key(s_on)
    names = {e["name"] for e in TRACER.events()}
    assert {"scheduler.step", "scheduler.place", "placement.search"} <= names


def test_netsim_makespan_identical_with_tracing():
    rng = np.random.default_rng(0)
    src = rng.integers(0, 4, (40, 3))
    dst = rng.integers(0, 4, (40, 3))
    vol = rng.random(40) + 0.1
    paths = build_paths((4, 4, 4), (src, dst, vol))
    r_off = simulate_flows(paths)
    TRACER.enable(clear=True)
    r_on = simulate_flows(paths)
    TRACER.disable()
    assert r_on.makespan == r_off.makespan
    assert np.array_equal(r_on.completion, r_off.completion)
    assert any(e["name"] == "netsim.drain" for e in TRACER.events())


def test_planner_table_identical_with_tracing():
    from repro.launch.planner import format_table, plan_model

    p_off = plan_model("granite-3-8b", 64, shape="decode_32k", simulate_top_k=0)
    TRACER.enable(clear=True)
    p_on = plan_model("granite-3-8b", 64, shape="decode_32k", simulate_top_k=0)
    TRACER.disable()
    assert format_table(p_off) == format_table(p_on)
    assert any(e["name"] == "planner.price" for e in TRACER.events())


def test_chrome_trace_round_trip_and_nesting():
    jobs = [
        JobRequest(i, 64, duration=2.0, arrival=0.5 * i) for i in range(12)
    ]
    TRACER.enable(clear=True)
    simulate_queue((16, 16, 16), jobs, ContentionScoredPolicy(),
                   contention="simulated")
    TRACER.disable()
    doc = json.loads(json.dumps(obs.export_chrome_trace()))
    events = doc["traceEvents"]
    assert events and all(e["ph"] == "X" for e in events)
    names = {e["name"] for e in events}
    assert {"scheduler.step", "scheduler.place", "placement.search",
            "netsim.drain"} <= names
    _assert_proper_nesting(events)
    # the scheduler.place spans nest inside scheduler.step wall-clock
    steps = [e for e in events if e["name"] == "scheduler.step"]
    places = [e for e in events if e["name"] == "scheduler.place"]
    for p in places:
        assert any(
            s["ts"] <= p["ts"] and p["ts"] + p["dur"] <= s["ts"] + s["dur"] + 1
            for s in steps
        )


def test_export_chrome_trace_to_file(tmp_path):
    TRACER.enable(clear=True)
    with TRACER.span("a"):
        pass
    TRACER.disable()
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(path)
    doc = json.loads(path.read_text())
    assert [e["name"] for e in doc["traceEvents"]] == ["a"]


# ---------------------------------------------------------------------------
# The layers below scheduler.step: reservation scan, backfill, geometry
# ranking and the backend's call/fetch split, on a Mira-size stream.
# ---------------------------------------------------------------------------
NEW_SPANS = ("scheduler.reserve", "scheduler.backfill", "allocation.rank",
             "xla.call", "xla.fetch")


def _blocking_mira_stream():
    """80 jobs on Mira's (4,4,3,2) midplane torus, arriving fast enough that
    heads block: 63 reservation scans and 87 backfill tries."""
    return generate_scenario((4, 4, 3, 2), 80, seed=0, failure_rate=0.01,
                             burst_gap=8.0)


def test_reserve_spans_count_scans_and_probes(monkeypatch):
    import repro.network.scheduler as sch

    calls = {"reserve": 0, "grids": 0}
    reservation, fits = sch.SchedulerService._reservation, sch.FitTest.__call__

    def counted_reservation(self, *a, **k):
        calls["reserve"] += 1
        return reservation(self, *a, **k)

    def counted_fits(self, grid):
        calls["grids"] += 1
        return fits(self, grid)

    monkeypatch.setattr(sch.SchedulerService, "_reservation", counted_reservation)
    monkeypatch.setattr(sch.FitTest, "__call__", counted_fits)
    TRACER.enable(clear=True)
    run_scenario(_blocking_mira_stream(), ContentionScoredPolicy(), backfill=True)
    TRACER.disable()
    events = TRACER.events()
    reserves = [e for e in events if e["name"] == "scheduler.reserve"]
    assert calls["reserve"] > 20 and len(reserves) == calls["reserve"]
    # probes: the scratch grids the scan tested, one per search step
    assert sum(e["args"]["probes"] for e in reserves) == calls["grids"]
    for e in reserves:
        assert e["args"]["packed"]  # Mira's 96 midplanes keep the table
        assert 1 <= e["args"]["probes"] <= max(e["args"]["frees"], 1).bit_length()
    ranks = [e for e in events if e["name"] == "allocation.rank"]
    assert ranks and all(e["args"]["units"] >= 1 for e in ranks)
    _assert_proper_nesting(events)


def test_backfill_tries_are_the_place_spans_inside_backfill(monkeypatch):
    import importlib.util
    from pathlib import Path

    import repro.network.scheduler as sch

    # count the _try_start calls made by the backfill pass
    tried = {"n": 0, "in_pass": False}
    backfill, try_start = sch.SchedulerService._backfill, sch.SchedulerService._try_start

    def counted_backfill(self, t_res):
        tried["in_pass"] = True
        try:
            return backfill(self, t_res)
        finally:
            tried["in_pass"] = False

    def counted_try_start(self, queued):
        tried["n"] += tried["in_pass"]
        return try_start(self, queued)

    monkeypatch.setattr(sch.SchedulerService, "_backfill", counted_backfill)
    monkeypatch.setattr(sch.SchedulerService, "_try_start", counted_try_start)
    TRACER.enable(clear=True)
    service = run_scenario(_blocking_mira_stream(), ContentionScoredPolicy(),
                           backfill=True)
    TRACER.disable()
    bench = Path(__file__).resolve().parents[1] / "chipbench"
    monkeypatch.syspath_prepend(str(bench))
    spec = importlib.util.spec_from_file_location(
        "reader_backfill_tries", bench / "metrics" / "backfill_tries_per_event.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    per_event = reader.read({"units": [{"log": service.log}],
                             "spans": TRACER.events()})
    assert tried["n"] > 0
    assert per_event * len(service.log) == pytest.approx(tried["n"])


def test_scheduler_log_identical_with_tracing_when_heads_block():
    scenario = _blocking_mira_stream()
    s_off = run_scenario(scenario, ContentionScoredPolicy(), backfill=True)
    TRACER.enable(clear=True)
    s_on = run_scenario(scenario, ContentionScoredPolicy(), backfill=True)
    TRACER.disable()
    assert _log_key(s_off) == _log_key(s_on)
    assert any(e.kind == "start" for e in s_on.log)
    names = {e["name"] for e in TRACER.events()}
    assert {"scheduler.reserve", "scheduler.backfill", "allocation.rank"} <= names


def _profile(trace_dir, work):
    """Run ``work`` under jax's profiler (Python tracer off); returns its
    result and the names of the profiler's host-plane events."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True))[-1]
    host = [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events]
    return out, host


def _profiled_replay(trace_dir, traced):
    """Replay the blocking stream on the xla backend under the profiler;
    returns the service, the repro.obs spans and the host-plane names."""
    if traced:
        TRACER.enable(clear=True)
    try:
        service, host = _profile(trace_dir, lambda: run_scenario(
            _blocking_mira_stream(), ContentionScoredPolicy(), backfill=True,
            backend="xla"))
    finally:
        TRACER.disable()
    spans = TRACER.events()
    TRACER.clear()
    return service, spans, host


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    obs.clear_telemetry()
    return _profiled_replay(tmp_path_factory.mktemp("profile"), traced=True)


def test_spans_land_on_the_profilers_host_plane(profiled, tmp_path):
    from collections import Counter

    _, spans, host = profiled
    on_host = Counter(host)
    recorded = Counter(e["name"] for e in spans)
    for name in ("scheduler.step", "scheduler.place",
                 "backend.contention_field") + NEW_SPANS:
        label = name.replace(".", ":", 1)
        assert recorded[name] > 0, name
        assert on_host[label] == recorded[name], name
    # tracing off: the profiler sees none of the program's spans
    _, spans_off, host_off = _profiled_replay(tmp_path, traced=False)
    assert spans_off == []
    labels = {n.replace(".", ":", 1) for n in recorded}
    assert not labels & set(host_off)


def test_backend_fetch_nests_inside_its_dispatch(profiled):
    _, spans, _ = profiled
    dispatches = [e for e in spans if e["name"] == "backend.contention_field"]
    for child in ("xla.call", "xla.fetch"):
        kids = [e for e in spans if e["name"] == child]
        assert len(kids) == len(dispatches)
        assert all(
            any(d["ts"] <= k["ts"] and k["ts"] + k["dur"] <= d["ts"] + d["dur"] + 1
                for d in dispatches)
            for k in kids
        )


def test_existing_span_readers_ignore_the_new_spans(profiled, monkeypatch):
    import importlib.util
    from pathlib import Path

    service, spans, _ = profiled
    bench = Path(__file__).resolve().parents[1] / "chipbench"
    monkeypatch.syspath_prepend(str(bench))
    kept = [e for e in spans if e["name"] not in NEW_SPANS]
    assert len(kept) < len(spans)
    for metric in ("scheduler_ms_per_event", "place_ms_per_event",
                   "backend_ms_per_event"):
        spec = importlib.util.spec_from_file_location(
            f"reader_{metric}", bench / "metrics" / f"{metric}.py")
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        units = [{"log": service.log}]
        full = reader.read({"units": units, "spans": spans})
        assert full > 0, metric
        assert full == reader.read({"units": units, "spans": kept}), metric


def test_timers_and_undotted_spans_reach_the_profiler(tmp_path):
    def work():
        TRACER.enable(clear=True)
        with obs.timer("serve.decode.step"), TRACER.span("plain"):
            pass
        TRACER.disable()
        with obs.timer("serve.prefill"):  # tracing off: no annotation
            pass

    _, host = _profile(tmp_path, work)
    assert {"serve:decode.step", "plain"} <= set(host)
    assert "serve:prefill" not in host


def test_obs_stays_importable_without_jax():
    import subprocess
    import sys

    code = (
        "import sys, repro.obs as obs\n"
        "obs.enable_tracing()\n"
        "with obs.trace('scheduler.step'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules\n"
        "assert [e['name'] for e in obs.export_chrome_trace()['traceEvents']]"
        " == ['scheduler.step']\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True)


# ---------------------------------------------------------------------------
# Metrics registry + scheduler metrics.
# ---------------------------------------------------------------------------
def test_registry_basics():
    reg = MetricsRegistry()
    reg.counter("hits", route="a").incr()
    reg.counter("hits", route="a").incr(2)
    reg.counter("hits", route="b").incr()
    reg.gauge("temp").set(3.5)
    h = reg.histogram("lat")
    for v in (0.002, 0.02, 5.0):
        h.observe(v)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["hits{route=a}"] == 3
    assert snap["counters"]["hits{route=b}"] == 1
    assert snap["gauges"]["temp"] == 3.5
    assert snap["histograms"]["lat"]["count"] == 3
    assert snap["histograms"]["lat"]["sum"] == pytest.approx(5.022)


def test_scheduler_metrics_match_result_exactly():
    scenario = generate_scenario((8, 8, 8), 40, seed=9, failure_rate=0.003)
    service = run_scenario(scenario, IsoperimetricPolicy(), backfill=True)
    reg = scheduler_metrics(service)
    snap = reg.snapshot()
    # per-job gauges equal the SimulationResult fields bit-for-bit
    # (last segment wins for re-queued jobs, as in the snapshot)
    last = {}
    for job in service.result().jobs:
        last[job.placement.job_id] = job
    assert last, "scenario scheduled no jobs"
    for job_id, job in last.items():
        key = f"scheduler.job.bisection_efficiency{{job={job_id}}}"
        assert snap["gauges"][key] == job.bisection_efficiency
    events = sum(
        v for k, v in snap["counters"].items()
        if k.startswith("scheduler.events{")
    )
    assert events == len(service.log)
    assert snap["histograms"]["scheduler.wait_time"]["count"] > 0
    assert 0.0 < snap["gauges"]["scheduler.utilization"] <= 1.0


def test_replay_reproduces_metrics_snapshot():
    scenario = generate_scenario((8, 8, 8), 30, seed=11, failure_rate=0.002)
    service = run_scenario(scenario, IsoperimetricPolicy())
    replayed = replay_events((8, 8, 8), IsoperimetricPolicy(), service.log)
    snap_a = scheduler_metrics(service).snapshot()
    snap_b = scheduler_metrics(replayed).snapshot()
    assert snap_a == snap_b


# ---------------------------------------------------------------------------
# Contention attribution: the paper's avoidable-contention pair.
# ---------------------------------------------------------------------------
def test_avoidable_contention_acceptance_pair():
    machine = MachineState((16, 16, 16))
    assert machine.allocate(0, (8, 8, 8)) is not None
    assert machine.allocate(1, (16, 16, 2)) is not None
    report = attribute_contention(machine)
    by_id = {j.job_id: j for j in report.jobs}
    good, bad = by_id[0], by_id[1]
    # (8,8,8) is the isoperimetric optimum: nothing avoidable, certified
    assert good.avoidable_ratio == pytest.approx(1.0)
    assert good.avoidable_excess == pytest.approx(0.0)
    assert good.certified
    # (16,16,2) carries 2x the optimal pairing load (paper Theorem 3.1)
    assert bad.avoidable_ratio == pytest.approx(2.0)
    assert bad.avoidable_excess == pytest.approx(1.0)
    assert bad.certified
    assert bad.optimal_geometry is not None
    assert sorted(bad.optimal_geometry) == [8, 8, 8]


def test_attribution_sums_to_machine_field():
    machine = MachineState((16, 16, 16))
    machine.allocate(0, (8, 8, 8))
    machine.allocate(1, (16, 16, 2))
    report = attribute_contention(machine)
    per_job = sum(j.self_load + j.cross_load for j in report.jobs)
    assert per_job == pytest.approx(float(machine.traffic_loads().sum()))
    assert report.total_load == pytest.approx(float(machine.traffic_loads().sum()))
    assert report.hotspots
    # hotspot shares attribute load to the spilling job
    top = report.hotspots[0]
    assert top.load == pytest.approx(report.max_link_load)


def test_attribute_traffic_validates_shapes():
    with pytest.raises(ValueError):
        attribute_traffic((4, 4), {0: np.zeros((2, 2, 4, 4, 9))})


def test_dashboard_renders():
    machine = MachineState((16, 16, 16))
    machine.allocate(0, (8, 8, 8))
    machine.allocate(1, (16, 16, 2))
    report = attribute_contention(machine)
    text = render_dashboard(report)
    assert "job" in text and "avoid" in text
    assert "(16, 16, 2)" in text or "16x16x2" in text
    doc = json.loads(report.to_json())
    assert doc["dims"] == [16, 16, 16]
    assert len(doc["jobs"]) == 2
