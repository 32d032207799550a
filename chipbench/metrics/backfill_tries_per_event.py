"""Backfill candidates handed to placement per event: the ``scheduler.place``
spans inside ``scheduler.backfill`` spans on the same thread (each job behind a
blocked head that ends by its reservation is tried once, in one
``scheduler.place``)."""

import bisect

from spans import events


def read(ctx):
    passes = {}
    for s in ctx["spans"]:
        if s["name"] == "scheduler.backfill":
            passes.setdefault(s["tid"], []).append((s["ts"], s["ts"] + s["dur"]))
    if not passes:
        return None
    for v in passes.values():
        v.sort()
    tries = 0
    for s in ctx["spans"]:
        if s["name"] == "scheduler.place" and s["tid"] in passes:
            v = passes[s["tid"]]
            i = bisect.bisect_right(v, (s["ts"], float("inf"))) - 1
            tries += i >= 0 and s["ts"] + s["dur"] <= v[i][1]
    return tries / events(ctx)
