"""Launcher: production meshes, sharding inference, dry-run, train/serve
drivers.  NOTE: dryrun.py sets XLA_FLAGS at import — never import it from
test or benchmark code."""
from .mesh import dp_axes, make_production_mesh  # noqa: F401
