"""Host time of one step of the serve loop: the ``serve.prefill`` and
``serve.decode`` spans over the ``steps`` they count (device time, the
dispatch of each step and the per-token argmax's wait).  A decode step of
it is the time between two tokens, which ``decision_p95_ms`` reads at its
95th percentile."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s["name"] in ("serve.prefill", "serve.decode")
             and "steps" in s.get("args", {})]
    steps = sum(s["args"]["steps"] for s in spans)
    return 1e-3 * sum(s["dur"] for s in spans) / steps if steps else None
