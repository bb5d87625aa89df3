"""The exchange's least time as a percentage of its device time.

Least time per exchange: for dispatch ``S`` and combine ``S^T`` each,
the larger of the interconnect bound and the HBM bound
(``chipbench.roofline.exchange_least_s``), from the count matrix alone.
Device time: the union of every device operation on the chip that was
busy longest over the traced exchanges.  A lower bound over a union of
operations cannot pass 100%."""
from chipbench import roofline


def read(ctx):
    seg = ctx.traces.get("lib")
    if seg is None or not seg["reduction"]["busy_s"]:
        return None
    rb = ctx.layer["row_bytes"]
    least = sum(roofline.exchange_least_s(M, rb, ctx.peaks)[0]
                for d in seg["draws"]
                for M in (ctx.layer["pool"][d], ctx.layer["pool"][d].T))
    return roofline.share_pct(least, max(seg["reduction"]["busy_s"]
                                         .values()))
