"""1 - busy / traced window on the one chip, over the traced passes."""
from chipbench import trace_reduce


def read(ctx):
    seg = ctx.traces.get("lib")
    if seg is None or not seg["reduction"]["busy_s"]:
        return None
    red = seg["reduction"]
    return 1.0 - trace_reduce.mean_over_chips(red["busy_s"]) / red["window_s"]
