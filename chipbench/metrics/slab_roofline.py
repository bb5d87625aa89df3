"""The slab data plane's HBM least time as a percentage of its device
time: per pass, the rows the rank sends off-chip read once and the rows
it receives from other chips written once, for dispatch ``S`` and
combine ``S^T`` (``chipbench.roofline.slab_least_s``), over the union of
the chip's device operations in the traced passes."""
from chipbench import roofline


def read(ctx):
    seg = ctx.traces.get("lib")
    if seg is None or not seg["reduction"]["busy_s"]:
        return None
    rb, pool, ranks = (ctx.layer["row_bytes"], ctx.layer["pool"],
                       ctx.layer["ranks"])
    least = sum(roofline.slab_least_s(M, ranks[d], rb, ctx.peaks)
                for d in seg["draws"] for M in (pool[d], pool[d].T))
    return roofline.share_pct(least, max(seg["reduction"]["busy_s"]
                                         .values()))
