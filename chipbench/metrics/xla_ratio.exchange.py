"""Device time of the library's exchange over that of XLA's
``all_to_all`` at capacity on the same draws (dispatch then combine), on
the chip busy longest in each traced segment."""


def read(ctx):
    lib, xla = ctx.traces.get("lib"), ctx.traces.get("xla")
    if lib is None or xla is None:
        return None
    t_lib = max(lib["reduction"]["busy_s"].values()) / len(lib["draws"])
    t_xla = max(xla["reduction"]["busy_s"].values()) / len(xla["draws"])
    return t_lib / t_xla if t_xla > 0 else None
