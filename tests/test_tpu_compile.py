"""Every Pallas kernel compiles for a TPU v5e at real widths.

Interpret mode (``tests/test_kernels.py``) checks what the kernels
compute; it cannot see what the TPU compiler refuses: blocks whose last
two dimensions are not legal tiles, or more VMEM than a kernel may use.
These tests compile each kernel for a described (not attached) v5e and
assert that the Pallas kernel is in the program (``tpu_custom_call``).

Shapes: internlm2-1.8b attention heads (16 query heads, 8 KV heads,
head_dim 128) over the paged pool ``chip_smoke.py`` serves from (8 slots
x 512 tokens in 16-token pages) with its packed step's 129 query tokens,
and over the benchmark cells' pools (1,184 pages of 16, 32 slots, each
cell's ``max_len``) with their packed step's 513 and decode step's 32
query tokens; the paged kernel also at mixtral-8x22b's group of 6 and
recurrentgemma-2b's group of 10 with head_dim 256; mamba2-130m SSD heads
(24 heads, head_dim 64, state 128, chunk 256).

The kernel modules are called directly: ``kernels.ops`` would take its
CPU branch here.  The topology is described inside a fixture, never at
import, so that only the test worker given this file loads the TPU
compiler.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import masked_accum as ma
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_chunk as sc
from repro.serve.kv import KVCacheSpec

# internlm2-1.8b heads over chip_smoke.py's serving pool
T, H, KV, D = 129, 16, 8, 128
PAGES, PAGE, SLOTS = 256, 16, 8
# the benchmark cells' pool (bench/traffic/{chat,docs}.json): pages, slots,
# max_len per cell, and the packed and decode-only step capacities
CELL_PAGES, CELL_SLOTS = 1184, 32
CELL_MAX_LEN = {"chat": 2560, "docs": 4096}
CELL_TOKENS = (513, 32)
# mamba2-130m SSD heads
SSD_H, SSD_P, SSD_N, SSD_L = 24, 64, 128, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _paged(kv_dtype, t=T, pages=PAGES, slots=SLOTS, blocks=PAGES // SLOTS,
           heads=(H, KV, D), window=0, softcap=0.0):
    h, kv, d = heads

    def call(q, k, v, tables, pos, slots, *scales):
        ks, vs = scales or (None, None)
        return fa.paged_flash_attention(
            q, k, v, tables, pos, slots, window=window, softcap=softcap,
            k_scale=ks, v_scale=vs,
        )

    shapes = [
        ((t, h, d), jnp.bfloat16),
        ((pages, PAGE, kv, d), kv_dtype),
        ((pages, PAGE, kv, d), kv_dtype),
        ((slots, blocks), jnp.int32),
        ((t,), jnp.int32),
        ((t,), jnp.int32),
    ]
    if kv_dtype == jnp.int8:
        shapes += [((pages, PAGE, kv), jnp.float32)] * 2
    return call, shapes


def _paged_cell(cell, t):
    spec = KVCacheSpec(num_slots=CELL_SLOTS, max_len=CELL_MAX_LEN[cell],
                       layout="paged", page_size=PAGE, num_pages=CELL_PAGES)
    blocks = spec.blocks_per_slot(get_config("internlm2_1_8b"))
    return _paged(jnp.bfloat16, t=t, pages=CELL_PAGES, slots=CELL_SLOTS, blocks=blocks)


def _paged_model(arch, t, pages, kv_dtype=jnp.bfloat16, softcap=0.0):
    """Another model's attention widths and window over a docs-like pool
    (32 slots, ``max_len`` 4,096): its group size sets the block shape."""
    cfg = get_config(arch)
    spec = KVCacheSpec(num_slots=CELL_SLOTS, max_len=CELL_MAX_LEN["docs"],
                       layout="paged", page_size=PAGE, num_pages=pages)
    return _paged(kv_dtype, t=t, pages=pages, slots=CELL_SLOTS,
                  blocks=spec.blocks_per_slot(cfg),
                  heads=(cfg.n_heads, cfg.n_kv_heads, cfg.hd),
                  window=cfg.sliding_window, softcap=softcap)


def _flash(segmented):
    b, s = (2, 1024) if segmented else (1, 2048)
    shapes = [((b, H, s, D), jnp.bfloat16), ((b, KV, s, D), jnp.bfloat16),
              ((b, KV, s, D), jnp.bfloat16)]
    if not segmented:
        return fa.flash_attention, shapes

    def call(q, k, v, q_seg, kv_seg):
        return fa.flash_attention(q, k, v, q_segment_ids=q_seg, kv_segment_ids=kv_seg)

    return call, shapes + [((b, s), jnp.int32)] * 2


CASES = {
    "paged_flash_attention_bf16": lambda: _paged(jnp.bfloat16),
    "paged_flash_attention_int8": lambda: _paged(jnp.int8),
    **{f"paged_flash_attention_{cell}_{t}": functools.partial(_paged_cell, cell, t)
       for cell in CELL_MAX_LEN for t in CELL_TOKENS},
    # group 6 (mixtral-8x22b: 48/8 heads of 128, window 4,096) at the cells'
    # pool, bf16 and int8 pages
    **{f"paged_flash_attention_g6_{t}": functools.partial(
        _paged_model, "mixtral_8x22b", t, CELL_PAGES) for t in CELL_TOKENS},
    "paged_flash_attention_g6_int8": functools.partial(
        _paged_model, "mixtral_8x22b", 513, CELL_PAGES, kv_dtype=jnp.int8),
    # group 10 and head_dim 256 (recurrentgemma-2b: 10/1 heads, window
    # 2,048) with a softcap; one KV head of 256 stages twice a head's bytes
    # at internlm2 widths, so half the cells' pool
    **{f"paged_flash_attention_g10_d256_{t}": functools.partial(
        _paged_model, "recurrentgemma_2b", t, CELL_PAGES // 2, softcap=30.0)
       for t in CELL_TOKENS},
    "ssd_segment": lambda: (sc.ssd_segment, [
        ((SSD_L, SSD_H, SSD_P), jnp.float32), ((SSD_L, SSD_H), jnp.float32),
        ((SSD_L, SSD_H), jnp.float32), ((SSD_L, SSD_N), jnp.float32),
        ((SSD_L, SSD_N), jnp.float32), ((SSD_L,), jnp.int32),
    ]),
    "ssd_chunk": lambda: (sc.ssd_chunk, [
        ((2, 4, SSD_L, SSD_H, SSD_P), jnp.float32), ((2, 4, SSD_L, SSD_H), jnp.float32),
        ((2, 4, SSD_L, SSD_H), jnp.float32), ((2, 4, SSD_L, SSD_N), jnp.float32),
        ((2, 4, SSD_L, SSD_N), jnp.float32),
    ]),
    "flash_attention": lambda: _flash(segmented=False),
    "flash_attention_segmented": lambda: _flash(segmented=True),
    "rmsnorm": lambda: (rn.rmsnorm, [((4096, 2048), jnp.bfloat16), ((2048,), jnp.float32)]),
    "masked_accum": lambda: (ma.masked_accum, [
        ((1024 * 4096,), jnp.float32), ((1024 * 4096,), jnp.bfloat16), ((), jnp.float32),
    ]),
    "masked_accum_tree": lambda: (
        lambda acc, grad, keep: ma.masked_accum_tree(
            {"w": acc, "b": acc[:D]}, {"w": grad, "b": grad[:D]}, keep),
        [((4096, 1024), jnp.float32), ((4096, 1024), jnp.bfloat16), ((), jnp.float32)],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_pools_stay_in_hbm(one_chip):
    """The paged kernel stages each head's pool with its own DMAs from
    HBM.  A pool that XLA placed in VMEM ahead of the call (memory space
    1, ``S(1)`` in the layout) would move that transfer into the op that
    produces it, out of the kernel's device time.  The pin is a trade kept
    for ``bench/metrics/paged_attn_roofline.docs.py``, which charges the
    pool's reads to the kernel; this test goes with the pin once that
    reader no longer depends on placement."""
    call, shapes = _paged_cell("docs", 32)

    def step(q, k, v, *rest):  # the pools come from a producer, as in the model
        return call(q, k * 1, v * 1, *rest)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(step).lower(*args).compile().as_text()
    # each instruction's result type, by name: "%name = type op(...)"
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\S+) ", hlo, re.M))
    kernel = re.findall(r"= \S+ custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", hlo)
    assert len(kernel) == 1, kernel
    pool = f"bf16[{KV},{CELL_PAGES * PAGE},{D}]"
    operands = [re.sub(r"/\*index=\d+\*/", "", op) for op in kernel[0].split(", ")]
    pools = [types[op] for op in operands if types.get(op, "").startswith(pool)]
    assert len(pools) == 2, (kernel[0], pools)
    assert not any("S(1)" in t for t in pools), pools
