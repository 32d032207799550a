"""Self times from the ``repro.obs`` tracer's spans (Chrome trace events:
``name``, ``ts`` and ``dur`` in microseconds, ``tid``, ``args``)."""

from __future__ import annotations

import bisect
from typing import Callable, List


def named(spans: List[dict], test: Callable[[dict], bool]) -> List[dict]:
    return [s for s in spans if test(s)]


def inside(parents: List[dict], children: List[dict]) -> float:
    """Microseconds of ``children`` that lie inside some span of ``parents`` on
    the same thread (parents of one name never overlap on a thread)."""
    by_tid = {}
    for p in parents:
        by_tid.setdefault(p["tid"], []).append((p["ts"], p["ts"] + p["dur"]))
    for v in by_tid.values():
        v.sort()
    total = 0.0
    for c in children:
        spans = by_tid.get(c["tid"])
        if not spans:
            continue
        i = bisect.bisect_right(spans, (c["ts"], float("inf"))) - 1
        if i >= 0 and c["ts"] + c["dur"] <= spans[i][1]:
            total += c["dur"]
    return total


def self_ms(spans: List[dict], parent: Callable[[dict], bool],
            child: Callable[[dict], bool]) -> float:
    """Milliseconds of the ``parent`` spans less the ``child`` spans inside them."""
    ps = named(spans, parent)
    return 1e-3 * (sum(p["dur"] for p in ps) - inside(ps, named(spans, child)))


def events(ctx: dict) -> int:
    """Scheduler events logged by the window's replays."""
    return sum(len(u["log"]) for u in ctx["units"])
