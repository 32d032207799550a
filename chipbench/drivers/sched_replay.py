"""Scheduler traffic: seeded job streams replayed through ``SchedulerService``.

A unit is one stream of ``jobs`` jobs replayed from an empty machine: every
input event (arrival, midplane failure, repair) in time order is handed to
the service (``submit`` / ``inject_failure`` / ``inject_reclaim``) and
followed by ``run(until=<its time>)``; after the last, ``run()`` drains the
machine.  The service is the paper's policy as Mira would run it:
``ContentionScoredPolicy`` with EASY backfill, contention fields on the
``xla`` backend.  Each stream has its own seed, drawn from ``--seed`` and
the stream's index; set-up replays a short stream from a seed of its own.

The stream's laws are copied from ``repro.network.scheduler.generate_scenario``
so that the yardstick does not move with the program: bursts of
Poisson(``burst_size``)+1 jobs at exponential gaps, sizes Pareto
(``tail_index``) snapped down to the machine's partition sizes of at most
``max_fraction`` of it, log-normal durations (sigma 0.75), midplane
failures as a Poisson process, each
repaired ``repair_delay`` later.  The burst gap is set from the mix's
offered ``load`` (busy midplanes over all of them).  The jobs are one draw
per mix; each stream deals their durations out anew (``make_stream``).

The check replays ``sample_streams`` streams of the window, drawn from the
seed, through the plain reference (``reference/scheduler.py``) with the
same calls, and counts the events of the two logs that differ: kind, time,
job, and for a start the placement and its predicted contention.
"""

from __future__ import annotations

import time

import numpy as np

WARM_INDEX = 1 << 30  # stream index of the set-up replay, apart from the window's
WARM_JOBS = 100  # jobs of the set-up replay
SIGMA = 0.75  # log-normal shape of the durations


def stream_seed(seed: int, index: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed, index])


def mean_size(sizes, tail_index: float) -> float:
    """Mean of a Pareto(tail_index) + 1 draw snapped down to ``sizes``."""
    s = sorted(sizes)
    tail = [x ** -tail_index for x in s] + [0.0]  # P(draw >= s_i)
    return sum(v * (tail[i] - tail[i + 1]) for i, v in enumerate(s))


def job_sizes(cfg: dict, mix: dict) -> list:
    """The partition sizes a job may take: at most ``max_fraction`` of the machine."""
    cap = max(1, int(mix["max_fraction"] * int(np.prod(cfg["dims"]))))
    return sorted(s for s in cfg["sizes"] if s <= cap)


def burst_gap(cfg: dict, mix: dict) -> float:
    """Mean gap between bursts that offers ``mix['load']`` of the machine."""
    units = int(np.prod(cfg["dims"]))
    work = ((mix["burst_size"] + 1) * mean_size(job_sizes(cfg, mix), mix["tail_index"])
            * mix["mean_duration"] * np.exp(SIGMA**2 / 2))
    return work / (mix["load"] * units)


def make_stream(cfg: dict, mix: dict, seed: np.random.SeedSequence) -> list:
    """The stream's input events in the order they are handed in:
    ``(time, kind, payload)`` with kind ``arrival`` (job, units, duration),
    ``fail`` or ``reclaim`` (a tuple of cells).

    Every stream of a mix holds the same work: the arrival times, the jobs'
    sizes and durations and the failures are one draw from the mix's
    ``base_seed``.  ``seed`` deals the durations out again among the jobs of
    each size.  Under these laws a stream's cost rests on a few episodes in
    which large jobs queue; streams drawn whole from different seeds cost up
    to three times one another, so the seed moves only what keeps the cost
    alike."""
    rng = np.random.default_rng(mix["base_seed"])
    dims = tuple(cfg["dims"])
    volumes = np.asarray(job_sizes(cfg, mix))
    gap = burst_gap(cfg, mix)
    times, sizes, durations, now = [], [], [], 0.0
    while len(sizes) < mix["jobs"]:
        now += float(rng.exponential(gap))
        for k in range(int(rng.poisson(mix["burst_size"])) + 1):
            if len(sizes) >= mix["jobs"]:
                break
            raw = float(rng.pareto(mix["tail_index"])) + 1.0
            sizes.append(int(volumes[np.searchsorted(volumes, raw, side="right") - 1]))
            durations.append(float(rng.lognormal(np.log(mix["mean_duration"]), SIGMA)))
            times.append(now + 1e-3 * k)  # stable order inside a burst
    events, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / mix["failure_rate"]))
        if t >= times[-1]:
            break
        cell = (tuple(int(rng.integers(d)) for d in dims),)
        events += [(t, "fail", cell), (t + mix["repair_delay"], "reclaim", cell)]

    deal = np.random.default_rng(seed)
    dealt = list(durations)
    for size in sorted(set(sizes)):
        jobs = [i for i, s in enumerate(sizes) if s == size]
        for i, j in zip(jobs, deal.permutation(jobs)):
            dealt[i] = durations[j]
    events += [(t, "arrival", (i, sizes[i], dealt[i])) for i, t in enumerate(times)]
    rank = {"arrival": 0, "fail": 1, "reclaim": 2}
    return sorted(events, key=lambda e: (e[0], rank[e[1]]))


def drive(service, stream: list, submit, clock=None):
    """Hand every input event to ``service`` and run it to the event's time;
    returns the decision time of each event (seconds) when ``clock`` is given."""
    times = []
    for t, kind, payload in stream:
        t0 = clock() if clock else 0.0
        if kind == "arrival":
            submit(service, t, payload)
        elif kind == "fail":
            service.inject_failure(t, payload)
        else:
            service.inject_reclaim(t, cells=payload)
        service.run(until=t)
        if clock:
            times.append(clock() - t0)
    service.run()
    return times


def _program_submit(service, t, payload):
    from repro.network.allocation import JobRequest

    job_id, units, duration = payload
    service.submit(JobRequest(job_id, units, duration=duration, arrival=t))


def _reference_submit(service, t, payload):
    job_id, units, duration = payload
    service.submit(job_id, units, duration, t)


def _service(cfg: dict):
    from repro.network.allocation import ContentionScoredPolicy
    from repro.network.scheduler import SchedulerService

    return SchedulerService(tuple(cfg["dims"]), ContentionScoredPolicy(),
                            backfill=True, backend="xla")


def replay(cfg: dict, stream: list) -> dict:
    import jax

    svc = _service(cfg)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("scheduler:replay"):
        decisions = drive(svc, stream, _program_submit, time.perf_counter)
    wall = time.perf_counter() - t0
    return {"wall_s": wall, "decisions": decisions, "log": svc.log,
            "jobs": sum(1 for e in stream if e[1] == "arrival")}


def warm_stream(cfg: dict, mix: dict, seed: int) -> list:
    """The first ``WARM_JOBS`` jobs of a stream, then one job of every size
    alone on the machine: every shape the window's streams can dispatch."""
    stream = make_stream(cfg, {**mix, "jobs": WARM_JOBS}, stream_seed(seed, WARM_INDEX))
    end = max(t for t, _, _ in stream)
    return stream + [(end + 1e4 * (k + 1), "arrival", (WARM_JOBS + k, size, 1.0))
                     for k, size in enumerate(job_sizes(cfg, mix))]


def setup(cfg: dict, mix: dict, seed: int) -> dict:
    replay(cfg, warm_stream(cfg, mix, seed))
    return {"cfg": cfg, "mix": mix, "seed": seed}


def unit(state: dict, index: int) -> dict:
    stream = make_stream(state["cfg"], state["mix"], stream_seed(state["seed"], index))
    rec = replay(state["cfg"], stream)
    rec["index"] = index
    return rec


def end_to_end(units: list) -> dict:
    events = sum(len(u["log"]) for u in units)
    decisions = np.concatenate([u["decisions"] for u in units])
    return {
        "metrics": {
            "sched_events_per_s": events / sum(u["wall_s"] for u in units),
            "decision_p95_ms": 1e3 * float(np.quantile(decisions, 0.95)),
        },
        "attempted": sum(u["jobs"] for u in units),
        "failed": sum(1 for u in units for e in u["log"] if e.kind == "reject"),
    }


def program_log(log) -> list:
    """The program's log in the reference's terms."""
    out = []
    for e in log:
        p = e.placement
        out.append((
            e.time, e.kind, e.job_id,
            tuple(p.oriented) if p else None, tuple(p.offset) if p else None,
            p.predicted_contention if p else None,
            tuple(tuple(c) for c in e.cells) if e.cells else None,
            e.reason,
        ))
    return out


def mismatches(a: list, b: list) -> int:
    """Events that differ between two logs, position by position, plus the
    difference in length."""
    return sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))


def sample(units: list, seed: int, n: int) -> list:
    """``n`` streams of the window (all, if it holds fewer), drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    picks = rng.choice(len(units), size=min(n, len(units)), replace=False)
    return [units[int(i)] for i in sorted(picks)]


def reference_log(cfg: dict, mix: dict, seed: int, index: int, scored: bool = True) -> list:
    from reference.scheduler import Service

    svc = Service(cfg["dims"], backfill=True, scored=scored)
    drive(svc, make_stream(cfg, mix, stream_seed(seed, index)), _reference_submit)
    return svc.log


def _compare(cfg, mix, checks, units, seed, produced) -> dict:
    """Events that differ from the reference's log, summed over the sample."""
    n = sum(mismatches(produced(u), reference_log(cfg, mix, seed, u["index"]))
            for u in sample(units, seed, checks["sample_streams"]))
    return {"log_mismatch": {"value": n, "limit": checks["limits"]["log_mismatch"]}}


def check(cfg: dict, mix: dict, checks: dict, units: list, seed: int) -> dict:
    return _compare(cfg, mix, checks, units, seed, lambda u: program_log(u["log"]))


def control(cfg: dict, mix: dict, checks: dict, units: list, seed: int) -> dict:
    """The control in the program's place: the reference with its guarantee of
    least-contention placement broken (first free placement instead)."""
    return _compare(cfg, mix, checks, units, seed,
                    lambda u: reference_log(cfg, mix, seed, u["index"], scored=False))
