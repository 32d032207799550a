"""The scheduler's reservation scan per event: ``scheduler.reserve`` spans (a
blocked head's pending frees replayed on a scratch grid until it fits), a part
of ``scheduler_ms_per_event``."""

from spans import events


def read(ctx):
    us = [s["dur"] for s in ctx["spans"] if s["name"] == "scheduler.reserve"]
    if not us:
        return None
    return 1e-3 * sum(us) / events(ctx)
