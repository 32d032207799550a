"""Share of the traced window in which no operation ran on the device, while
the serve loop ran: the host's dispatch, the per-token argmax's wait and
the launcher.  The idle between two steps (dispatch, the argmax's wait)
is a part of the time between two tokens (``decision_p95_ms``)."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
