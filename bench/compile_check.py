"""Compile each cell's step programs at real size for a described TPU v5e
(no chip attached): what the TPU compiler refuses here costs no chip
time.  Prints each program's device memory and whether the Pallas kernel
is in it.  Nothing runs, so nothing here is a measurement.

    JAX_PLATFORMS=cpu python bench/compile_check.py [cell ...]
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding  # noqa: E402

from bench import common  # noqa: E402
from bench.run import model_config  # noqa: E402
from bench.weights import abstract_params  # noqa: E402


def _with(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _report(name, compiled, t0):
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, total {total / 2**30:.2f} GiB; "
          f"Pallas kernel in program: {'tpu_custom_call' in hlo}; all-reduces: "
          f"{hlo.count('all-reduce(') + hlo.count('all-reduce-start(')}", flush=True)


def serve_programs(cell, conf, mix, dev):
    from repro.kernels import ops
    from repro.serve import KVCacheSpec, scheduler
    from repro.serve import packing

    # the program picks its XLA path when the host's backend is the CPU;
    # here the compile is for the chip, so take the chip's branch
    ops._default_interpret = lambda: False
    cfg = model_config(conf)
    e = mix["engine"]
    one = SingleDeviceSharding(dev)
    params = _with(abstract_params(cfg), one)
    spec = KVCacheSpec(num_slots=e["slots"], max_len=e["max_len"], layout="paged",
                       page_size=e["page_size"], num_pages=e["num_pages"])
    state = jax.eval_shape(lambda: spec.build(None, cfg).state)
    state = _with(state, one)
    for cap in (packing.packed_capacity(e["slots"], e["chunk_size"], e["token_budget"]),
                e["slots"]):
        vec = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one)
        t0 = time.perf_counter()
        compiled = scheduler._packed_engine_step.lower(
            params, cfg, state, vec, vec, vec, moe_impl="dense").compile()
        _report(f"{cell} packed step, capacity {cap}, {e['num_pages']} pages", compiled, t0)
    ref = common.reference_module(common.cell(cell)[0]["config"])
    toks = jax.ShapeDtypeStruct((e["max_len"],), jnp.int32, sharding=one)
    at = jax.ShapeDtypeStruct((mix["output"]["max"],), jnp.int32, sharding=one)
    keys = ("d_model", "n_heads", "n_kv_heads", "rope_theta")
    model = tuple((k, conf["model"][k]) for k in keys)
    for quant in (None, "fp8"):
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            compiled = ref._logits_at.lower(params, toks, at, model, quant).compile()
        _report(f"{cell} reference logits ({quant or 'float32'})", compiled, t0)


def train_programs(cell, conf, job, devices):
    from repro.core import DropConfig
    from repro.dist import Distribution
    from repro.dist.mesh import make_mesh
    from repro.models import InputShape

    cfg = model_config(conf)
    n = common.cell(cell)[0]["chips"]
    dist = Distribution(make_mesh((n,), ("data",), devices=devices[:n]))
    batch = job["workers"] * job["microbatches"] * job["rows"]
    shape = InputShape("bench", job["seq_len"], batch, "train",
                       microbatches=job["microbatches"])
    bundle = dist.train_step(
        cfg, shape, DropConfig(enabled=True, tau=job["tau"], normalize=job["normalize"]),
        n_workers=job["workers"], optimizer=job["optimizer"], lr=job["lr"],
        clip_norm=job["clip_norm"], weight_decay=job["weight_decay"])
    t0 = time.perf_counter()
    _report(f"{cell} SPMD train step on {n} chips", bundle.lower().compile(), t0)
    ref = common.reference_module(common.cell(cell)[0]["config"])
    mesh = jax.sharding.Mesh(devices[:n], ("data",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params = _with(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                                abstract_params(cfg)), rep)
    tok = jax.ShapeDtypeStruct((16, job["seq_len"]), jnp.int32, sharding=rows)
    wts = jax.ShapeDtypeStruct((16, job["seq_len"]), jnp.float32, sharding=rows)
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        compiled = ref._grad_chunk.lower(params, tok, wts, cfg.n_heads, None).compile()
    _report(f"{cell} reference gradient of 16 rows on {n} chips", compiled, t0)


def main(cells):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = common.benchmark()
    for w in bench["workloads"]:
        if cells and w["name"] not in cells:
            continue
        _, conf, mix = common.cell(w["name"])
        if mix["driver"] == "train_spmd":
            train_programs(w["name"], conf, mix, topo.devices)
        else:
            serve_programs(w["name"], conf, mix, topo.devices[0])


if __name__ == "__main__":
    main(sys.argv[1:])
