"""Model FLOP/s utilization of the traced window: the forward FLOPs of every
token the window's units fed through the decode step (prompt and generated
alike), from the shapes (``serve_cost.unit_flops``), over the window's
length times the chip's bf16 peak.  A faster step raises it and lowers
``decision_p95_ms``, which it moves."""

from serve_cost import unit_flops


def read(ctx):
    units = [u for u in ctx["units"] if "prompts" in u]
    if not units:
        return None
    flops = len(units) * unit_flops(ctx["config"], ctx["mix"])
    return 100.0 * flops / (ctx["trace"]["window_s"] * ctx["peaks"]["bf16_flops"])
