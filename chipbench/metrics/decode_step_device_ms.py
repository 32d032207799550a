"""Device time of one decode step: the ``jit_decode_step`` programs' time in
the traced window's device trace over their executions.  Most of the time
between two tokens (``decision_p95_ms``) is this step."""

from reduce import module_time


def read(ctx):
    n, seconds = module_time(ctx["trace"], "jit_decode_step")
    return 1e3 * seconds / n if n else None
