"""Every Pallas kernel compiles for a TPU v5e at real widths.

Interpret mode (``tests/test_kernels.py``) checks what the kernels
compute; it cannot see what the TPU compiler refuses: blocks whose last
two dimensions are not legal tiles, or more VMEM than a kernel may use.
These tests compile each kernel for a described (not attached) v5e and
assert that the Pallas kernel is in the program (``tpu_custom_call``).

Shapes: internlm2-1.8b attention heads (16 query heads, 8 KV heads,
head_dim 128) over the paged pool ``chip_smoke.py`` serves from (8 slots
x 512 tokens in 16-token pages) with its packed step's 129 query tokens;
mamba2-130m SSD heads (24 heads, head_dim 64, state 128, chunk 256).

The kernel modules are called directly: ``kernels.ops`` would take its
CPU branch here.  The topology is described inside a fixture, never at
import, so that only the test worker given this file loads the TPU
compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import masked_accum as ma
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_chunk as sc

# internlm2-1.8b heads over chip_smoke.py's serving pool
T, H, KV, D = 129, 16, 8, 128
PAGES, PAGE, SLOTS = 256, 16, 8
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


def _paged(kv_dtype):
    def call(q, k, v, tables, pos, slots, *scales):
        ks, vs = scales or (None, None)
        return fa.paged_flash_attention(
            q, k, v, tables, pos, slots, k_scale=ks, v_scale=vs
        )

    shapes = [
        ((T, H, D), jnp.bfloat16),
        ((PAGES, PAGE, KV, D), kv_dtype),
        ((PAGES, PAGE, KV, D), kv_dtype),
        ((SLOTS, PAGES // SLOTS), jnp.int32),
        ((T,), jnp.int32),
        ((T,), jnp.int32),
    ]
    if kv_dtype == jnp.int8:
        shapes += [((PAGES, PAGE, KV), jnp.float32)] * 2
    return call, shapes


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
