"""repro.dist — the distribution API for the whole codebase.

* ``repro.dist.mesh``     — mesh construction (Auto axis types).
* ``repro.dist.sharding`` — path-based sharding rules (params, opt state,
  decode caches, batches).
* ``repro.dist.api``      — ``Distribution``: mesh + rules + donation, and
  the single entry point for building sharded train/prefill/serve steps.
"""
from .api import Distribution, StepBundle
from .mesh import (
    HW,
    dp_axes,
    dp_size,
    make_dev_mesh,
    make_mesh,
    make_production_mesh,
    tp_size,
)
from .sharding import (
    batch_spec,
    cache_shardings,
    opt_shardings,
    param_shardings,
    spec_for_path,
)

__all__ = [
    "Distribution",
    "StepBundle",
    "HW",
    "dp_axes",
    "dp_size",
    "tp_size",
    "make_dev_mesh",
    "make_mesh",
    "make_production_mesh",
    "batch_spec",
    "cache_shardings",
    "opt_shardings",
    "param_shardings",
    "spec_for_path",
]
