"""One kind of timed work per module; a traffic file names its driver."""
import contextlib


def annotation(on: bool):
    """``jax.profiler.TraceAnnotation`` where ``on``, else a no-op span:
    the host spans the trace reduction attributes idle gaps to."""
    if not on:
        return lambda _name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation
