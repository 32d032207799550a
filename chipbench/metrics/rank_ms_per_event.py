"""Geometry ranking inside placement per event: ``allocation.rank`` spans (the
policy's preference list for a request) inside ``scheduler.place``, a part of
``place_ms_per_event``. The blocked head's own ranking, outside placement, is
left in ``scheduler_ms_per_event``."""

from spans import events, inside, named


def read(ctx):
    spans = ctx["spans"]
    ranks = named(spans, lambda s: s["name"] == "allocation.rank")
    if not ranks:
        return None
    places = named(spans, lambda s: s["name"] == "scheduler.place")
    return 1e-3 * inside(places, ranks) / events(ctx)
