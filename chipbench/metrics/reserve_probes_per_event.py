"""``first_fit`` probes of the reservation scan per event: the ``probes`` count
of the ``scheduler.reserve`` spans, a count the profiler cannot inflate."""

from spans import events


def read(ctx):
    probes = [s.get("args", {}).get("probes", 0) for s in ctx["spans"]
              if s["name"] == "scheduler.reserve"]
    if not probes:
        return None
    return sum(probes) / events(ctx)
