"""``Distribution`` — the one object the rest of the codebase talks to.

A ``Distribution`` bundles a mesh with the path-based sharding rules and
the donation policy, and exposes the only supported way to build SPMD
steps: ``train_step`` / ``prefill_step`` / ``serve_step`` return a
``StepBundle`` whose function is jitted with the right in/out shardings
and donated buffers, plus the abstract inputs needed to ``lower()`` it
without allocating anything (the dry-run path).

    dist = Distribution.for_devices()                  # dev mesh
    dist = Distribution.production(multi_pod=True)     # 2x16x16 pods
    dist = Distribution.from_spec("4,2")               # --mesh CLI flag

    bundle = dist.train_step(cfg, shape, drop)
    params, opt_state, metrics = bundle(params, opt_state, batch, lat)
    lowered = bundle.lower()                           # dry-run / HLO

Callers that only need placements use ``param_shardings`` /
``opt_shardings`` / ``cache_shardings`` / ``batch_shardings`` — thin,
mesh-bound views over ``repro.dist.sharding``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib
from . import sharding as rules

PyTree = Any


@dataclasses.dataclass
class StepBundle:
    """A jitted SPMD step plus everything needed to run or lower it."""

    fn: Callable  # jitted; call through the bundle, which sets the mesh
    mesh: Any
    abstract_inputs: Tuple  # ShapeDtypeStructs accepted by ``fn``
    in_shardings: Tuple
    out_shardings: Any
    opt: Any = None  # train steps carry their optimizer

    # ``jax.set_mesh`` makes the mesh visible to the model code while it
    # traces (``models.moe._current_mesh``): activation constraints and the
    # shard_map'd MoE layer read it there.
    def __call__(self, *args):
        with jax.set_mesh(self.mesh):
            return self.fn(*args)

    def lower(self):
        with jax.set_mesh(self.mesh):
            return self.fn.lower(*self.abstract_inputs)


@dataclasses.dataclass(frozen=True)
class Distribution:
    """Mesh + sharding rules + donation policy, as one value."""

    mesh: Any
    donate: bool = True

    # -- constructors -------------------------------------------------------

    @classmethod
    def for_devices(
        cls, n_devices: Optional[int] = None, model_parallel: int = 1, **kw
    ) -> "Distribution":
        return cls(mesh_lib.make_dev_mesh(n_devices, model_parallel), **kw)

    @classmethod
    def production(cls, multi_pod: bool = False, **kw) -> "Distribution":
        return cls(mesh_lib.make_production_mesh(multi_pod=multi_pod), **kw)

    @classmethod
    def from_spec(cls, spec: Union[str, Tuple[int, ...]], **kw) -> "Distribution":
        """Parse a ``--mesh`` flag: "4,2" -> (data=4, model=2); "2,16,16"
        -> (pod, data, model)."""
        dims = tuple(int(x) for x in spec.split(",")) if isinstance(spec, str) else tuple(spec)
        names = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}
        if len(dims) not in names:
            raise ValueError(f"--mesh wants 1-3 comma-separated dims, got {spec!r}")
        return cls(mesh_lib.make_mesh(dims, names[len(dims)]), **kw)

    # -- topology -----------------------------------------------------------

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def dp_size(self) -> int:
        """Data parallelism == the DropCompute worker count W."""
        return mesh_lib.dp_size(self.mesh)

    @property
    def tp_size(self) -> int:
        return mesh_lib.tp_size(self.mesh)

    # -- placements ---------------------------------------------------------

    def spec_for_path(self, path: str, shape) -> P:
        return rules.spec_for_path(path, shape, self.mesh)

    def param_shardings(self, params: PyTree) -> PyTree:
        return rules.param_shardings(params, self.mesh)

    def opt_shardings(self, opt_state: PyTree) -> PyTree:
        return rules.opt_shardings(opt_state, self.mesh)

    def cache_shardings(self, cache: PyTree, shard_seq: bool = False) -> PyTree:
        return rules.cache_shardings(cache, self.mesh, shard_seq=shard_seq)

    def batch_shardings(self, cfg, shape) -> PyTree:
        from ..launch import steps as S  # lazy: steps imports repro.dist

        return S.batch_shardings(cfg, shape, self.mesh)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard(self, tree: PyTree, shardings: Optional[PyTree] = None) -> PyTree:
        """Place a concrete pytree onto the mesh (params by default)."""
        if shardings is None:
            shardings = self.param_shardings(tree)
        return jax.device_put(tree, shardings)

    # -- step builders (the single entry point for SPMD programs) ----------

    def train_step(self, cfg, shape, drop, **kw) -> StepBundle:
        """Jitted DropCompute train step, sharded by the rules.

        ``kw`` forwards to ``launch.steps.make_train_step`` (optimizer, lr,
        clip_norm, moe_impl, state_dtype, accum_dtype, cast_params_once,
        weight_decay).  ``n_workers`` defaults to the mesh's dp size.
        """
        from ..launch import steps as S

        n_workers = kw.pop("n_workers", None) or self.dp_size
        opt, step = S.make_train_step(cfg, shape, drop, n_workers, **kw)
        params_abs = S.abstract_params(cfg)
        opt_abs = S.abstract_opt_state(cfg, opt, params_abs)
        specs = S.input_specs(cfg, shape, self.mesh, n_workers=n_workers)
        b_sh = S.batch_shardings(cfg, shape, self.mesh, n_workers=n_workers)
        p_sh = self.param_shardings(params_abs)
        o_sh = self.opt_shardings(opt_abs)
        in_sh = (p_sh, o_sh, b_sh["batch"], b_sh["latencies"])
        out_sh = (p_sh, o_sh, None)
        jitted = jax.jit(
            step,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=(0, 1) if self.donate else (),
        )
        return StepBundle(
            fn=jitted,
            mesh=self.mesh,
            abstract_inputs=(params_abs, opt_abs, specs["batch"], specs["latencies"]),
            in_shardings=in_sh,
            out_shardings=out_sh,
            opt=opt,
        )

    def prefill_step(self, cfg, shape, **kw) -> StepBundle:
        from ..launch import steps as S

        step = S.make_prefill_step(cfg, **kw)
        params_abs = S.abstract_params(cfg)
        specs = S.input_specs(cfg, shape, self.mesh)
        b_sh = S.batch_shardings(cfg, shape, self.mesh)
        p_sh = self.param_shardings(params_abs)
        in_sh = (p_sh, b_sh["batch"])
        jitted = jax.jit(step, in_shardings=in_sh)
        return StepBundle(
            fn=jitted,
            mesh=self.mesh,
            abstract_inputs=(params_abs, specs["batch"]),
            in_shardings=in_sh,
            out_shardings=None,
        )

    def serve_step(self, cfg, shape, shard_seq: Optional[bool] = None, **kw) -> StepBundle:
        from ..launch import steps as S

        step = S.make_serve_step(cfg, **kw)
        params_abs = S.abstract_params(cfg)
        cache_abs = S.abstract_cache(cfg, shape)
        specs = S.input_specs(cfg, shape, self.mesh)
        b_sh = S.batch_shardings(cfg, shape, self.mesh)
        if shard_seq is None:
            shard_seq = shape.global_batch < self.dp_size
        p_sh = self.param_shardings(params_abs)
        c_sh = self.cache_shardings(cache_abs, shard_seq=shard_seq)
        in_sh = (p_sh, c_sh, b_sh["token"], b_sh["pos"])
        out_sh = (None, c_sh)
        jitted = jax.jit(
            step,
            in_shardings=in_sh,
            out_shardings=out_sh,
            donate_argnums=(1,) if self.donate else (),
        )
        return StepBundle(
            fn=jitted,
            mesh=self.mesh,
            abstract_inputs=(params_abs, cache_abs, specs["token"], specs["pos"]),
            in_shardings=in_sh,
            out_shardings=out_sh,
        )
