"""Launchers: production meshes, the multi-pod dry-run, roofline analysis,
and train/serve entry points.

NOTE: do not import .dryrun from here — it sets XLA_FLAGS at import time.
"""
from .mesh import (  # noqa: F401
    ShardingRules,
    activation_spec,
    batch_axes_for,
    batch_shardings,
    cache_shardings,
    make_local_mesh,
    make_production_mesh,
    param_shardings,
)
