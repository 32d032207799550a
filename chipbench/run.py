"""Run one benchmark cell once on the chip this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is data found by name: ``BENCHMARK.json``
names the cell's configuration and traffic mix; the configuration is
``chipbench/configs/<config>.json``; the mix is
``chipbench/traffic/<traffic>.json``, which names its driver
``chipbench/drivers/<driver>.py``; each per-layer metric is read by
``chipbench/metrics/<metric>.py``; the check's limits are
``chipbench/checks/<cell>.json``.

A run: find the TPU (exit non-zero, printing no result, without one),
warm up the cell's shapes (set-up), then start units of traffic (for the
scheduler, one replayed job stream) until the time left is shorter than
the last unit took.  With ``--trace 1`` the window runs under
the profiler and the ``repro.obs`` tracer and the per-layer metrics are
reported; otherwise the end-to-end ones.  After the window (a unit keeps
nothing of the program's state past its end) the timed path's answers are
checked against the plain reference.  The last line of standard output is
the result, as JSON.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

OUT = BENCH / ".out"  # traces and the TPU runtime's logs: inside the checkout
TRACE_DIR = OUT / "trace"
os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def resolve(workload: str) -> dict:
    """The cell's entries and files, by the names in ``BENCHMARK.json``."""
    spec = read_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    mix = read_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": read_json(ROOT / conf["file"]),
        "mix": mix,
        "checks": read_json(BENCH / "checks" / f"{workload}.json"),
        "driver": load_module(BENCH / "drivers" / f"{mix['driver']}.py"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def find_device(chips: int) -> dict:
    """The device JAX found; exits non-zero unless it is a TPU with enough chips
    and a row in the table of peaks."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} count={info['count']}",
          flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU found: JAX found {info['platform']!r}")
    if info["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX found {info['count']}")
    peaks = read_json(BENCH / "peaks.json")["devices"]
    if info["kind"] not in peaks:
        raise SystemExit(f"device kind {info['kind']!r} has no row in chipbench/peaks.json")
    return info


class CompileCounter:
    """Counts XLA compiles while ``active``: backend compile requests that the
    persistent cache did not answer."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = self.hits = 0
        self.active = False
        event = "/jax/core/compile/backend_compile_duration"  # also fires on cache hits

        def on_duration(name, *_a, **_k):
            if self.active and name == event:
                self.requests += 1

        def on_event(name, *_a, **_k):
            if self.active and name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @property
    def compiles(self) -> int:
        return self.requests - self.hits


def backend_jit_compiles() -> float:
    from repro.obs import REGISTRY

    return sum(v for k, v in REGISTRY.snapshot()["counters"].items()
               if k.startswith("backend.jit_compiles"))


def judge(checks: dict) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_window(driver, state, seconds: float, trace: bool):
    """Units until the time left is shorter than the last unit took (at least one)."""
    import jax

    units = []
    start = time.perf_counter()
    deadline = start + seconds
    with jax.profiler.TraceAnnotation("chipbench:window") if trace else contextlib.nullcontext():
        while True:
            left = deadline - time.perf_counter()
            if units and left < units[-1]["wall_s"]:
                break
            units.append(driver.unit(state, len(units)))
    return units, time.perf_counter() - start


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, cache: bool = True, overrides=None) -> dict:
    """One run of one cell; returns the result object (also what ``main`` prints).

    ``require_tpu=False`` and ``cache=False`` skip the look for a chip and the
    persistent compile cache, and ``overrides`` replaces entries of the
    resolved cell (a smaller configuration): the tests drive the rest of a
    run that way on the CPU."""
    r = resolve(workload)
    r.update(overrides or {})
    chips = r["cell"]["chips"]
    if require_tpu:
        device = find_device(chips)
    else:
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    import jax

    if cache:
        from repro.utils.env import enable_compile_cache

        # every program goes to the cache, so a later run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        print(f"compile cache: {enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    driver = r["driver"]
    state = driver.setup(r["config"], r["mix"], seed)
    setup_s = time.perf_counter() - T0
    print(f"setup_s: {setup_s:.3f}", flush=True)

    spans = None
    jit_before = backend_jit_compiles()
    if trace:
        from repro.obs import TRACER

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACER.enable(clear=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    counter.active = True
    try:
        units, window_s = run_window(driver, state, seconds, trace)
    finally:
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
            TRACER.disable()
            spans = TRACER.events()
            TRACER.clear()
    jit_in_window = backend_jit_compiles() - jit_before
    print(f"compiles in window: {counter.compiles} (XLA), {jit_in_window:g} "
          f"(backend.jit_compiles); units: {len(units)} in {window_s:.3f} s", flush=True)

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    work = driver.end_to_end(units)

    result_metrics = {}
    breakdown = None
    if trace:
        import reduce as red

        tr = red.reduce(red.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        peaks = r.get("peaks") or read_json(BENCH / "peaks.json")["devices"][device["kind"]]
        ctx = {"config": r["config"], "mix": r["mix"], "units": units, "trace": tr,
               "spans": spans, "peaks": peaks}
        for m in r["per_layer"]:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in r["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else work["metrics"][m["name"]]
            result_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.perf_counter()
    checks = driver.check(r["config"], r["mix"], r["checks"], units, seed)
    correct = judge(checks)
    print(f"check took {time.perf_counter() - t_check:.3f} s; run {time.perf_counter() - T0:.3f} s",
          flush=True)
    print(f"correct: {correct}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    out = {
        "correct": correct,
        "attempted": work["attempted"],
        "failed": work["failed"],
        "metrics": result_metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
