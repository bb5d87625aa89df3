"""Row bytes the exchange's ppermutes move over the cell's row bytes
(``hidden_size`` x itemsize): 1.0 where the kernels take the rows as they
are, 3072 / 2688 where bf16 rows of 2688 lanes are held lane-padded.
The moved bytes are the program's own count, the gauge
``moved_row_bytes`` its planner sets on every plan lookup in
``repro.obs.metrics.REGISTRY``; a program without that gauge reads
nothing."""


def read(ctx):
    row_bytes = ctx.layer.get("row_bytes")
    if not row_bytes:
        return None
    try:
        from repro.obs.metrics import REGISTRY
    except ImportError:
        return None
    moved = REGISTRY.snapshot()["gauges"].get("moved_row_bytes")
    return moved / row_bytes if moved else None
