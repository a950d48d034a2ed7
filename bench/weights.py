"""Random weights from the seed, made on the device in one jitted call.

The benchmark, not the program, makes the weights: every leaf of the
program's parameter tree is drawn from ``fold_in(seed key, leaf index)``
as a normal with standard deviation ``1/sqrt(fan in)`` (the fan-in axes
of each leaf name come from the configuration file), norm scales are
ones and biases zeros.  The plain references read the same tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import seed_key


def _name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _stacked(path) -> bool:
    return any(getattr(p, "key", None) == "groups" for p in path)


def abstract_params(cfg):
    from repro.models.model import init_params

    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def make_params(cfg, conf: dict, seed: int, shardings=None):
    """The program's parameter tree for ``cfg``, filled from ``seed`` in
    ``cfg.param_dtype``; ``shardings`` places it (one device by default)."""
    abstract = abstract_params(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    fan_axes = conf["fan_in_axes"]
    ones, zeros = set(conf["ones"]), set(conf["zeros"])
    specs = []
    for path, leaf in flat:
        name = _name(path)
        shape = leaf.shape[1:] if _stacked(path) else leaf.shape
        if name in ones:
            specs.append(("ones", 0.0))
        elif name in zeros:
            specs.append(("zeros", 0.0))
        elif name in fan_axes:
            fan = math.prod(shape[a] for a in fan_axes[name])
            specs.append(("normal", 1.0 / math.sqrt(fan)))
        else:
            raise KeyError(f"no init rule for parameter {name!r} "
                           f"({jax.tree_util.keystr(path)}) in the config file")

    def build(key):
        out = []
        for i, ((_, leaf), (kind, std)) in enumerate(zip(flat, specs)):
            if kind == "ones":
                x = jnp.ones(leaf.shape, leaf.dtype)
            elif kind == "zeros":
                x = jnp.zeros(leaf.shape, leaf.dtype)
            else:
                k = jax.random.fold_in(key, i)
                x = (jax.random.normal(k, leaf.shape, jnp.float32) * std).astype(leaf.dtype)
            out.append(x)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
