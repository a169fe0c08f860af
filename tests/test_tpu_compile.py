"""Ahead-of-time compiles of the main path's Pallas kernels for a described
TPU v5e chip, at published head geometry (hd=128, bf16, B=8).

Nothing runs: the TPU compiler installed with libtpu compiles for a chip
that is described, not attached, and refuses what the chip's compiler would
refuse (block tiling, VMEM use). Interpret-mode tests cannot see either.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# (name, query heads, KV heads) of the served edge and cloud models
GEOMETRY = [("qwen2-vl-2b", 12, 2), ("qwen2.5-vl-7b", 28, 4)]
B, HD, T, PAGE = 8, 128, 2048, 256
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lowered = fn.lower(*args, interpret=False, **static)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("name,h,kh", GEOMETRY)
def test_paged_decode_compiles_for_v5e(one_chip, name, h, kh):
    pool = 1 + B * T // PAGE
    _compile(ops.decode_attention_paged, one_chip,
             ((B, 1, h, HD), BF16), ((pool, PAGE, kh, HD), BF16),
             ((pool, PAGE, kh, HD), BF16), ((B, T // PAGE), jnp.int32),
             ((B,), jnp.int32), ((B, T), jnp.int32))


@pytest.mark.parametrize("name,h,kh", GEOMETRY)
def test_dense_decode_compiles_for_v5e(one_chip, name, h, kh):
    _compile(ops.decode_attention, one_chip,
             ((B, 1, h, HD), BF16), ((B, T, kh, HD), BF16),
             ((B, T, kh, HD), BF16), ((B,), jnp.int32), ((B, T), jnp.int32))


@pytest.mark.parametrize("name,h,kh", GEOMETRY)
def test_flash_attention_compiles_for_v5e(one_chip, name, h, kh):
    s = 1024
    _compile(ops.flash_attention, one_chip, ((B, s, h, HD), BF16),
             ((B, s, kh, HD), BF16), ((B, s, kh, HD), BF16))


@pytest.mark.parametrize("b,hw", [(1, (1024, 1024)), (B, (1024, 1024)),
                                  (B, (333, 517)), (1, (64, 64))])
def test_image_stats_compiles_for_v5e(one_chip, b, hw):
    _compile(ops.image_stats, one_chip, ((b, *hw), jnp.float32))
