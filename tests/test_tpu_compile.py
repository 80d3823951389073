"""The serving path's Pallas kernels compile for a TPU v5e at Qwen3-1.7B widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, which catches what interpret mode cannot (block shapes Mosaic
refuses, VMEM overuse). The topology is described inside a fixture, never at
import, so every xdist worker collects the same tests and only the worker
that runs this file loads the TPU library. Skips where no TPU compiler is
installed.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention

CFG = get_config("qwen3-1.7b")
KV = CFG.num_kv_heads
QP = CFG.num_heads // CFG.num_kv_heads
HD = CFG.head_dim
PAGE = 16          # build_real_engine's default block_size
NUM_BLOCKS = 1041  # paged pool chip_smoke.py serves with, scratch block included


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe a v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batch,pages", [(1, 1), (16, 32)])
def test_paged_attention_compiles_for_v5e(one_chip, batch, pages):
    pool = _spec(one_chip, (NUM_BLOCKS, KV, PAGE, HD))
    compiled = paged_attention.lower(
        _spec(one_chip, (batch, KV, QP, HD)), pool, pool,
        _spec(one_chip, (batch, pages), jnp.int32),
        _spec(one_chip, (batch,), jnp.int32), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("length", [16, 1024])
def test_flash_prefill_compiles_for_v5e(one_chip, length):
    kv = _spec(one_chip, (1, KV, length, HD))
    compiled = flash_prefill.lower(
        _spec(one_chip, (1, KV, length, QP, HD)), kv, kv,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
