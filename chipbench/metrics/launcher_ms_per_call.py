"""The launcher's time per ``serve()`` call: the ``serve.init`` (model build,
parameters drawn under ``jit``) and ``serve.cache`` (cache allocation)
spans, per ``serve.init`` span.  Set-up serves one call, so it moves
``setup_s``; it lies outside the token stream that ``decision_p95_ms``
times."""


def read(ctx):
    inits = [s["dur"] for s in ctx["spans"] if s["name"] == "serve.init"]
    if not inits:
        return None
    caches = [s["dur"] for s in ctx["spans"] if s["name"] == "serve.cache"]
    return 1e-3 * (sum(inits) + sum(caches)) / len(inits)
