"""Reduction of a profiler trace to the numbers the benchmark reports.

A trace is read once into plain event lists (``load``) and reduced by pure
functions over them, so the arithmetic can be checked on a hand-made trace:

* the device's busy time: the union of the intervals in which an operation
  ran on it, clipped to the window;
* the idle share: 1 - busy / window;
* device time per jitted program (``XLA Modules`` line) and per operation
  (``XLA Ops`` line, loops left out of the ranking as they hold the rest);
* the longest idle gaps, each labelled with the benchmark's own host
  annotation that covers most of it (``layer:what`` names, written by
  ``jax.profiler.TraceAnnotation`` from the benchmark's files).

Times are nanoseconds on the trace's clock; results are seconds.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[str, float, float]  # (name, start_ns, duration_ns)

WINDOW = "chipbench:window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Operations that hold other operations of the same line (a loop's body):
# busy, but not counted among the operations that took the most time.
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"^(%[\w.\-]+) = .*?([a-z][\w\-]*)\(")


def op_name(name: str) -> Tuple[str, str]:
    """(short name, opcode) of an ``XLA Ops`` event: ``%while.54 = (...) while(...)``
    gives ``("%while.54", "while")``; a name that is not HLO text is its own."""
    m = _HLO.match(name)
    return (m.group(1), m.group(2)) if m else (name, "")


def load(trace_dir: str) -> Dict[str, List[Interval]]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``.

    ``ops`` and ``modules`` come from the device planes (``/device:...``),
    ``host`` from the host plane: every annotation whose name has a ``:``
    (the benchmark's ``layer:what`` names), on any thread."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out: Dict[str, List[Interval]] = {"ops": [], "modules": [], "host": [], "devices": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            out["devices"].append((plane.name, 0.0, 0.0))
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    out[key].extend((e.name, e.start_ns, e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events if ":" in e.name
                    and not e.name.startswith("$")
                )
    return out


def window_bounds(host: Sequence[Interval]) -> Tuple[float, float]:
    """The benchmark's window annotation, as (start_ns, end_ns)."""
    spans = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def merge(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals clipped to [lo, hi], sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_intervals(ev: Dict[str, List[Interval]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Device busy intervals: operations where the trace has them, else programs."""
    src = ev["ops"] or ev["modules"]
    return merge(((s, s + d) for _, s, d in src), lo, hi)


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of the window between busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Tuple[float, float], host: Sequence[Interval]) -> str:
    """The annotation that covers most of the gap (the shorter on a tie);
    ``idle`` when the host was inside none of the benchmark's annotations."""
    best, key = "idle", (0.0, 0.0)
    g0, g1 = gap
    for name, s, d in host:
        if name == WINDOW:
            continue
        cover = min(g1, s + d) - max(g0, s)
        if cover > 0 and (cover, -d) > key:
            best, key = name, (cover, -d)
    return best


def reduce(ev: Dict[str, List[Interval]], top: int = 10) -> dict:
    """Every trace number the benchmark reports, in seconds."""
    lo, hi = window_bounds(ev["host"])
    busy = busy_intervals(ev, lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    window_ns = hi - lo
    per_module: Dict[str, List[float]] = {}
    for name, s, d in ev["modules"]:
        if lo <= s < hi:
            per_module.setdefault(name, []).append(d)
    per_op: Dict[str, float] = {}
    for name, s, d in ev["ops"]:
        short, opcode = op_name(name)
        if lo <= s < hi and opcode not in CONTAINERS:
            per_op[short] = per_op.get(short, 0.0) + d
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "modules": {n: {"count": len(v), "seconds": sum(v) * 1e-9} for n, v in per_module.items()},
        "device_ops": [[n, t * 1e-9] for n, t in sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label(g, ev["host"]), (g[1] - g[0]) * 1e-9] for g in idle],
    }


def module_time(trace: dict, prefix: str) -> Tuple[int, float]:
    """(executions, device seconds) of the jitted programs whose name starts
    with ``prefix`` (``jit_decode_step`` matches ``jit_decode_step(123)``)."""
    n, t = 0, 0.0
    for name, v in trace["modules"].items():
        if name.startswith(prefix):
            n += v["count"]
            t += v["seconds"]
    return n, t
