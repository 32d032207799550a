"""The decode step's share of its HBM roofline: the least bytes a step moves,
from the configuration's and the traffic's shapes (``serve_cost``: weights
once per use, SSM and conv state read and written, keys and values of the
mean cached length read), over the chip's HBM bandwidth, over
``decode_step_device_ms``.  The step is bound by HBM (about 32 FLOP per
byte against the chip's 240).  No Pallas kernel is on this path: the jitted
step stands in for the kernel layer.  It moves ``decision_p95_ms``, like
``decode_step_device_ms``."""

from reduce import module_time
from serve_cost import mean_step_bytes


def read(ctx):
    n, seconds = module_time(ctx["trace"], "jit_decode_step")
    if not n:
        return None
    least_s = mean_step_bytes(ctx["config"], ctx["mix"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (seconds / n)
