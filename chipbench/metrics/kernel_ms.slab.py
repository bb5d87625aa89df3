"""Device milliseconds of the program's named slab kernels per pass, on
the cell's one chip.  A kernel's entry in the reduction is
``<name>/custom-call`` for a name in the program's ``KERNEL_NAMES``; a
program whose kernels carry no such name reads nothing."""


def kernel_names() -> tuple:
    try:
        from repro.kernels.ragged_gather.kernel import KERNEL_NAMES
    except ImportError:
        return ()
    return KERNEL_NAMES


def read(ctx):
    seg = ctx.traces.get("lib")
    if seg is None or not seg["reduction"]["busy_s"]:
        return None
    red = seg["reduction"]
    chip = max(red["busy_s"], key=red["busy_s"].get)
    names = set(kernel_names())
    found = [s for op, s in red["ops_s"].get(chip, {}).items()
             if op.split("/")[0] in names]
    return 1e3 * sum(found) / len(seg["draws"]) if found else None
