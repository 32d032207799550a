"""Placement's host time per event: ``scheduler.place`` spans (policy, geometry
ranking, ``placement.search``) less the compiled backend's ``backend.*`` spans
inside them."""

from spans import events, self_ms


def read(ctx):
    ms = self_ms(ctx["spans"], lambda s: s["name"] == "scheduler.place",
                 lambda s: s["name"].startswith("backend."))
    return ms / events(ctx)
