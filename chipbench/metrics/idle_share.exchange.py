"""1 - busy / traced window, the mean over the cell's chips, over the
traced exchanges."""
from chipbench import trace_reduce


def read(ctx):
    seg = ctx.traces.get("lib")
    if seg is None or not seg["reduction"]["busy_s"]:
        return None
    red = seg["reduction"]
    return 1.0 - trace_reduce.mean_over_chips(red["busy_s"]) / red["window_s"]
