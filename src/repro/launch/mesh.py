"""Production meshes.  A FUNCTION, not a module-level constant, so importing
this module never touches jax device state (spec requirement)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 'pod' axis (2 pods =
    512 chips).  The dry-run forces 512 host devices via XLA_FLAGS before
    any jax import (see dryrun.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """Batch/FSDP axes: everything that is not tensor-parallel."""
    return tuple(a for a in mesh.axis_names if a != "model")

