"""Event-sourced scheduler service: exact incremental fields, deterministic
event ordering, failures/preemption/reclaim, backpressure, and replay
determinism (PR 7's tentpole + satellites)."""

import numpy as np
import pytest

from repro.core.bgq import MIRA_SCHEDULER_PARTITIONS
from repro.network import (
    IsoperimetricPolicy,
    JobRequest,
    ListPolicy,
    MachineState,
    SchedulerService,
    apply_monitor_failures,
    generate_scenario,
    replay_events,
    run_scenario,
    simulate_queue,
)
from repro.network.allocation import Placement
from repro.network.fabric import HyperXFabric
from repro.network.geometry import canonical, sub_cuboids, volume
from repro.network.placement import (
    FitTest,
    first_fit,
    int_base_loads,
    placement_cells,
    placement_loads,
)
from repro.network.scheduler import RECLAIM, time_close, time_eps, time_le
from repro.runtime.fault_tolerance import HeartbeatMonitor


# ---------------------------------------------------------------------------
# Satellite 1: exact incremental traffic fields.
# ---------------------------------------------------------------------------
def test_int_base_loads_is_exact_integer_scaling():
    for dims, oriented in [
        ((4, 4, 4), (2, 2, 2)),
        ((4, 4, 4), (4, 2, 1)),
        ((8, 4, 4), (2, 2, 2)),
        ((4, 4), (2, 2)),
    ]:
        n = int(np.prod(oriented))
        int_field = int_base_loads(dims, oriented)
        assert int_field.dtype == np.int64
        float_field = placement_loads(dims, oriented, (0,) * len(dims))
        # Same support exactly, same values up to one float rounding.
        assert ((int_field > 0) == (float_field > 0)).all()
        assert np.allclose(int_field / (2.0 * n), float_field)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_incremental_field_equals_fresh_recompute(seed):
    """Random alloc/release stream: the incrementally maintained background
    is bit-identical to a fresh machine recombining only the survivors, and
    allclose to the float per-placement sum with identical support."""
    rng = np.random.default_rng(seed)
    dims = (4, 4, 4)
    geoms = [(1, 1, 1), (2, 1, 1), (2, 2, 1), (2, 2, 2), (4, 2, 1), (4, 2, 2)]
    m = MachineState(dims)
    live = []
    jid = 0
    for step in range(120):
        if live and rng.random() < 0.45:
            k = live.pop(int(rng.integers(len(live))))
            m.release(k)
        else:
            p = m.allocate(jid, geoms[int(rng.integers(len(geoms)))])
            if p is not None:
                live.append(jid)
                jid += 1
        incremental = m.traffic_loads()
        fresh = MachineState(dims)
        for k in live:
            p = m.placements[k]
            fresh.commit(k, p.geometry, p.oriented, p.offset)
        assert np.array_equal(incremental, fresh.traffic_loads()), step
        float_sum = np.zeros_like(incremental)
        for k in live:
            p = m.placements[k]
            float_sum += placement_loads(dims, p.oriented, p.offset)
        assert np.allclose(incremental, float_sum)
        assert ((incremental > 0) == (float_sum > 0)).all()


def test_traffic_loads_exclude_is_exact():
    m = MachineState((4, 4, 4))
    for jid, g in enumerate([(2, 2, 2), (4, 2, 1), (2, 2, 1)]):
        assert m.allocate(jid, g) is not None
    background = m.traffic_loads(exclude=1)
    fresh = MachineState((4, 4, 4))
    for jid in (0, 2):
        p = m.placements[jid]
        fresh.commit(jid, p.geometry, p.oriented, p.offset)
    assert np.array_equal(background, fresh.traffic_loads())


# ---------------------------------------------------------------------------
# Satellite 2: deterministic (time, kind, seq) ordering, scale-aware clock.
# ---------------------------------------------------------------------------
def test_time_eps_is_scale_aware():
    # At t ~ 2^26 one ulp is ~1.5e-8: the historical fixed 1e-12 cannot
    # merge adjacent floats there, the scale-aware tolerance can.
    t = float(2**26) + 0.125
    below = np.nextafter(t, 0.0)
    assert abs(t - below) > 1e-12
    assert time_close(t, below)
    assert time_le(t, below) and time_le(below, t)
    # Small clocks keep a tight absolute guard.
    assert time_eps(0.0) < 1e-13
    assert not time_close(1.0, 1.0 + 1e-9)


def test_tie_ordering_regression_100k_events():
    """>=1e5-event stream ending in an engineered tie: a completion and the
    next arrivals land one ulp apart at t ~ 2^26, where the old fixed-eps
    clock saw two instants (the arrival first — letting a zero-duration
    probe backfill ahead of the full-machine head).  The deterministic
    (time, kind, seq) ordering merges them and processes the completion
    first, so the head starts and the probe cannot jump it."""
    policy = ListPolicy({1: (1, 1, 1), 8: (2, 2, 2)})
    svc = SchedulerService((2, 2, 2), policy, backfill=True)
    n_filler = 33_400
    for k in range(n_filler):
        svc.submit(JobRequest(k, 1, duration=1.0, arrival=2.0 * k))
    scale = float(2**26)
    end_a = scale + 0.125  # exactly representable
    arr_b = float(np.nextafter(end_a, 0.0))  # one ulp before the completion
    assert abs(end_a - arr_b) > 1e-12  # the old absolute eps saw two instants
    assert time_close(end_a, arr_b)  # the scale-aware clock sees one
    job_a, job_b, job_c = n_filler, n_filler + 1, n_filler + 2
    svc.submit(JobRequest(job_a, 1, duration=10.125, arrival=scale - 10.0))
    svc.submit(JobRequest(job_b, 8, duration=7.0, arrival=arr_b))
    svc.submit(JobRequest(job_c, 1, duration=0.0, arrival=arr_b))
    svc.run()

    assert len(svc.log) >= 100_000
    starts = {
        e.job_id: e.seq for e in svc.log if e.kind == "start" and e.job_id >= n_filler
    }
    by_id = {j.request.job_id: j for j in svc.scheduled}
    # Complete(A) resolved before the tied arrivals: B holds the whole
    # machine from the tie instant, and the zero-duration probe C did not
    # backfill ahead of it.
    assert starts[job_b] < starts[job_c]
    assert time_close(by_id[job_b].start, end_a)
    assert time_close(by_id[job_c].start, by_id[job_b].end)
    assert not svc.rejected


# ---------------------------------------------------------------------------
# Satellite 3 + failure semantics.
# ---------------------------------------------------------------------------
def test_failure_unblocks_head_early_and_repair_revives_victim():
    policy = ListPolicy({4: (2, 2), 2: (2, 1)})
    svc = SchedulerService((2, 2), policy)
    svc.submit(JobRequest(0, 4, duration=100.0))  # fills the machine
    svc.submit(JobRequest(1, 2, duration=5.0, arrival=1.0))  # blocked head
    svc.inject_failure(10.0, [(0, 1)])  # evacuates job 0, kills one cell
    svc.inject_reclaim(50.0, cells=[(0, 1)])  # repair
    svc.run()

    segments = [(j.request.job_id, j.start, j.end) for j in svc.scheduled]
    # Job 0's first segment is truncated at the failure.
    assert segments[0] == (0, 0.0, 10.0)
    # The failure freed cells mid-run: job 1's stale reservation (t=100)
    # was invalidated and it started at the failure instant, not at 100.
    assert segments[1] == (1, 10.0, 15.0)
    # Job 0 requeued with its remaining 90 units, but (2,2) cannot fit a
    # 3-cell degraded machine: it waits for the scheduled repair.
    assert segments[2] == (0, 50.0, 140.0)
    assert not svc.rejected
    assert svc.failed_cells == set()  # repaired
    kinds = [e.kind for e in svc.log]
    assert "fail" in kinds and "preempt" in kinds and "reclaim" in kinds


def test_failure_without_repair_rejects_impossible_victim():
    policy = ListPolicy({4: (2, 2)})
    svc = SchedulerService((2, 2), policy)
    svc.submit(JobRequest(0, 4, duration=100.0))
    svc.inject_failure(10.0, [(1, 1)])
    svc.run()
    # No pending repair: the evacuated job can never fit the degraded
    # machine and is rejected rather than blocking the queue forever.
    assert svc.rejected == [0]
    assert svc.failed_cells == {(1, 1)}
    assert svc.machine.free_units == 3


# ---------------------------------------------------------------------------
# Satellite 4: edge cases.
# ---------------------------------------------------------------------------
def test_zero_duration_jobs_chain_at_one_instant():
    policy = ListPolicy({4: (2, 2)})
    svc = SchedulerService((2, 2), policy)
    for jid in range(3):
        svc.submit(JobRequest(jid, 4, duration=0.0))
    svc.run()
    assert [(j.request.job_id, j.start, j.end) for j in svc.scheduled] == [
        (0, 0.0, 0.0),
        (1, 0.0, 0.0),
        (2, 0.0, 0.0),
    ]
    assert svc.machine.free_units == 4


def test_arrival_exactly_at_completion_instant():
    policy = ListPolicy({4: (2, 2)})
    svc = SchedulerService((2, 2), policy)
    svc.submit(JobRequest(0, 4, duration=5.0))
    svc.submit(JobRequest(1, 4, duration=1.0, arrival=5.0))
    svc.run()
    # Complete ranks before Arrival inside one instant: job 1 starts
    # immediately at t=5 instead of waiting for a later wake.
    assert [(j.request.job_id, j.start) for j in svc.scheduled] == [(0, 0.0), (1, 5.0)]
    complete0 = next(e for e in svc.log if e.kind == "complete" and e.job_id == 0)
    arrival1 = next(e for e in svc.log if e.kind == "arrival" and e.job_id == 1)
    assert complete0.seq < arrival1.seq


def test_backfill_candidates_tied_at_reservation():
    policy = ListPolicy({1: (1, 1), 2: (2, 1), 4: (2, 2)})
    svc = SchedulerService((2, 2), policy, backfill=True)
    svc.submit(JobRequest(0, 2, duration=10.0))
    svc.submit(JobRequest(1, 4, duration=1.0, arrival=1.0))  # blocked, t_res=10
    # Both candidates end exactly at the reservation — both are admitted.
    svc.submit(JobRequest(2, 1, duration=9.0, arrival=1.0))
    svc.submit(JobRequest(3, 1, duration=9.0, arrival=1.0))
    svc.run()
    by_id = {j.request.job_id: j for j in svc.scheduled}
    assert by_id[2].start == 1.0 and by_id[3].start == 1.0
    assert by_id[1].start == 10.0  # the head was never delayed


def test_impossible_request_rejected_mid_stream():
    policy = IsoperimetricPolicy()
    svc = SchedulerService((2, 2), policy)
    svc.submit(JobRequest(0, 2, duration=2.0))
    svc.submit(JobRequest(1, 8, duration=1.0, arrival=0.5))  # > machine
    svc.submit(JobRequest(2, 2, duration=1.0, arrival=1.0))
    svc.run()
    assert svc.rejected == [1]
    reject = next(e for e in svc.log if e.kind == "reject")
    assert reject.reason == "impossible"
    assert {j.request.job_id for j in svc.scheduled} == {0, 2}


def test_preempt_then_reclaim_round_trip():
    policy = ListPolicy({2: (2, 1)})
    svc = SchedulerService((2, 2), policy)
    svc.submit(JobRequest(0, 2, duration=10.0))
    svc.inject_preempt(4.0, 0)
    svc.inject_reclaim(20.0, job_id=0)
    svc.run()
    segments = [(j.start, j.end) for j in svc.scheduled]
    # Suspended with 6 units remaining, resumed at the reclaim.
    assert segments == [(0.0, 4.0), (20.0, 26.0)]
    assert svc.machine.free_units == 4
    assert not svc._suspended


def test_event_log_replay_determinism():
    scenario = generate_scenario(
        (4, 4, 4), 40, seed=7, failure_rate=0.002, repair_delay=150.0
    )
    svc = run_scenario(scenario, IsoperimetricPolicy(), backfill=True)
    assert svc.scheduled  # the scenario actually exercises the machine
    replayed = replay_events((4, 4, 4), IsoperimetricPolicy(), svc.log, backfill=True)
    assert replayed.log == svc.log
    a, b = replayed.result(), svc.result()
    assert a.rejected == b.rejected
    assert [
        (j.request.job_id, j.start, j.end, j.placement) for j in a.jobs
    ] == [(j.request.job_id, j.start, j.end, j.placement) for j in b.jobs]


# ---------------------------------------------------------------------------
# Backpressure, priorities, the monitor bridge, scenarios.
# ---------------------------------------------------------------------------
def test_backpressure_sheds_past_bound():
    policy = ListPolicy({4: (2, 2)})
    svc = SchedulerService((2, 2), policy, max_waiting=1)
    svc.submit(JobRequest(0, 4, duration=10.0))
    svc.submit(JobRequest(1, 4, duration=1.0, arrival=1.0))  # waits
    svc.submit(JobRequest(2, 4, duration=1.0, arrival=2.0))  # shed
    svc.run()
    assert svc.shed == [2]
    assert svc.rejected == [2]
    shed = next(e for e in svc.log if e.kind == "reject")
    assert shed.reason == "backpressure"
    assert {j.request.job_id for j in svc.scheduled} == {0, 1}


def test_priority_preemption_and_requeue():
    policy = ListPolicy({4: (2, 2)})
    svc = SchedulerService((2, 2), policy, preempt_priority=True)
    svc.submit(JobRequest(0, 4, duration=100.0), priority=0)
    svc.submit(JobRequest(1, 4, duration=5.0, arrival=10.0), priority=5)
    svc.run()
    segments = [(j.request.job_id, j.start, j.end) for j in svc.scheduled]
    # The high-priority job evicts the running one and starts immediately;
    # the victim resumes its remaining 90 units after.
    assert segments == [(0, 0.0, 10.0), (1, 10.0, 15.0), (0, 15.0, 105.0)]
    evict = next(e for e in svc.log if e.kind == "preempt")
    assert evict.reason == "priority"


def test_heartbeat_monitor_feeds_failures():
    clock = [0.0]
    monitor = HeartbeatMonitor(["w00", "w01"], timeout=10.0, clock=lambda: clock[0])
    worker_cells = {"w00": (0, 0), "w01": (0, 1)}
    policy = ListPolicy({4: (2, 2), 2: (2, 1)})
    svc = SchedulerService((2, 2), policy)
    svc.submit(JobRequest(0, 4, duration=100.0))
    clock[0] = 25.0
    monitor.beat("w00")  # w01 went silent
    clock[0] = 31.0
    failed = apply_monitor_failures(svc, monitor, worker_cells, time=31.0)
    assert failed == [(0, 1)]
    svc.inject_reclaim(60.0, cells=failed)
    svc.run()
    by_start = [(j.request.job_id, j.start) for j in svc.scheduled]
    assert by_start == [(0, 0.0), (0, 60.0)]  # evacuated at 31, revived at 60


def test_scenario_generator_is_deterministic_and_feasible():
    a = generate_scenario((4, 4, 4), 30, seed=3, failure_rate=0.005)
    b = generate_scenario((4, 4, 4), 30, seed=3, failure_rate=0.005)
    assert a == b
    assert len(a.jobs) == 30
    assert all(1 <= j.units <= 16 for j in a.jobs)  # <= max_fraction * 64
    assert all(j.duration > 0 for j in a.jobs)
    c = generate_scenario((4, 4, 4), 30, seed=4, failure_rate=0.005)
    assert c != a


# ---------------------------------------------------------------------------
# Replay equivalence: the batch driver IS the service.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backfill", [False, True])
def test_simulate_queue_matches_manual_service(backfill):
    rng = np.random.default_rng(11)
    table = {1: (1, 1, 1, 1), 2: (2, 1, 1, 1), 4: (2, 2, 1, 1), 8: (4, 2, 1, 1)}
    sizes = list(table)
    jobs = [
        JobRequest(
            i,
            sizes[int(rng.integers(len(sizes)))],
            duration=float(rng.uniform(1.0, 20.0)),
            arrival=float(rng.uniform(0.0, 60.0)),
        )
        for i in range(60)
    ]
    res = simulate_queue((4, 4, 1, 1), jobs, ListPolicy(table), backfill=backfill)
    svc = SchedulerService((4, 4, 1, 1), ListPolicy(table), backfill=backfill)
    for _, req in sorted(enumerate(jobs), key=lambda t: (t[1].arrival, t[0])):
        svc.submit(req)
    direct = svc.run().result()
    assert [
        (j.request.job_id, j.start, j.end, j.placement.oriented, j.placement.offset)
        for j in res.jobs
    ] == [
        (j.request.job_id, j.start, j.end, j.placement.oriented, j.placement.offset)
        for j in direct.jobs
    ]
    assert res.rejected == direct.rejected


# ---------------------------------------------------------------------------
# The reservation scan: a monotone search over packed occupancy.
# ---------------------------------------------------------------------------
def _linear_reservation(svc, prefs):
    """The reservation scan as a linear replay, kept as the oracle: every
    pending free applied to a scratch grid in time order, ``first_fit``
    probed for each preferred geometry after each one."""
    if not prefs:
        return None
    frees = []
    for live in svc._live.values():
        frees.append((live.job.end, live.gen, live.job.placement))
    for time, _, seq, kind, data in svc._pending:
        if kind == RECLAIM and data[1]:
            frees.append((time, seq, tuple(data[1])))
    scratch = svc.machine.grid.copy()
    for time, _, freed in sorted(frees, key=lambda f: (f[0], f[1])):
        if isinstance(freed, Placement):
            scratch[
                placement_cells(svc.machine.dims, freed.oriented, freed.offset)
            ] = False
        else:
            for cell in freed:
                if tuple(cell) in svc.failed_cells:
                    scratch[tuple(cell)] = False
        if any(first_fit(scratch, g) is not None for g in prefs):
            return time
    if any(first_fit(scratch, g) is not None for g in prefs):
        return svc.now  # defensive: only asked after a failed allocate
    return None


@pytest.mark.parametrize(
    "machine, packed",
    [
        pytest.param((4, 4, 3, 2), True, id="mira"),
        pytest.param((7, 2, 2, 2), True, id="juqueen"),
        pytest.param(HyperXFabric((6, 3, 2)), True, id="hyperx-6x3x2"),
        pytest.param((24, 24, 12), False, id="above-table-budget"),
    ],
)
def test_reservation_search_matches_linear_replay(machine, packed):
    """Seeded running sets, failures and pending repairs: the searched
    reservation equals the linear replay's, ``None`` and ``now`` included,
    on the table path and, above its budget, on ``first_fit``'s."""
    dims = tuple(machine.dims if isinstance(machine, HyperXFabric) else machine)
    rng = np.random.default_rng(int(np.prod(dims)))

    def cuboid(cap=None):
        return canonical(int(rng.integers(1, min(a, cap or a) + 1)) for a in dims)

    # Jobs of at most 6 cells a side keep the big machine's traffic fields cheap.
    table = {volume(g): g for g in (cuboid(6) for _ in range(8))}
    svc = SchedulerService(machine, ListPolicy(table), backfill=True)
    for job_id in range(40):
        svc.submit(JobRequest(
            job_id,
            int(rng.choice(sorted(table))),
            duration=float(rng.choice([4.0, 8.0, 12.0])),  # tied ends
            arrival=float(rng.integers(0, 30)),
        ))
    cells = [tuple(int(rng.integers(a)) for a in dims) for _ in range(22)]
    for k, cell in enumerate(cells[:4]):
        when = float(rng.integers(0, 30))
        svc.inject_failure(when, [cell])
        if k:  # the first failed cell is never repaired
            svc.inject_reclaim(when + 15.0, cells=[cell])
    # Repairs of cells that have not failed free nothing.
    spurious = [cell for cell in cells[4:] if cell != cells[0]]
    for k in range(0, len(spurious), 3):
        svc.inject_reclaim(float(rng.integers(0, 50)), cells=spurious[k:k + 3])
    whole = canonical(dims)
    seen = set()
    for stop in list(range(0, 60, 4)) + [None]:
        svc.run(until=stop)
        for prefs in [[cuboid() for _ in range(int(rng.integers(1, 4)))]
                      for _ in range(3)] + [[whole]]:
            assert FitTest(dims, prefs).packed == packed
            expected = _linear_reservation(svc, prefs)
            assert svc._reservation(prefs) == expected, (stop, prefs)
            seen.add("none" if expected is None
                     else "now" if expected == svc.now else "free")
    assert seen == {"none", "now", "free"}


@pytest.mark.parametrize("units", sorted(MIRA_SCHEDULER_PARTITIONS))
def test_packed_fit_test_agrees_with_first_fit(units):
    """On random grids of Mira's midplane torus, at every occupancy, the
    packed test answers as ``first_fit`` does for every geometry of the
    partition size, alone and all together."""
    dims = (4, 4, 3, 2)
    geometries = list(sub_cuboids(dims, units))
    assert geometries
    rng = np.random.default_rng(units)
    grids = [np.zeros(dims, dtype=bool), np.ones(dims, dtype=bool)] + [
        rng.random(dims) < density for density in np.linspace(0.02, 0.98, 60)
    ]
    answers = set()
    for grid in grids:
        for prefs in [[g] for g in geometries] + [geometries]:
            fits = FitTest(dims, prefs)
            assert fits.packed
            expected = any(first_fit(grid, g) is not None for g in prefs)
            assert fits(grid) == expected
            answers.add(expected)
    assert answers == {True, False}
