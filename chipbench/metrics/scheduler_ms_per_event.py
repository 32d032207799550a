"""Self time of the scheduler's event loop per event: ``scheduler.step`` spans
less the ``scheduler.place`` spans inside them."""

from spans import events, self_ms


def read(ctx):
    ms = self_ms(ctx["spans"], lambda s: s["name"] == "scheduler.step",
                 lambda s: s["name"] == "scheduler.place")
    return ms / events(ctx)
