"""The serving path's Pallas kernels and paged decode step compile for a TPU
v5e at Qwen3-1.7B widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology, which catches what interpret mode cannot (block shapes Mosaic
refuses, VMEM overuse). The topology is described inside a fixture, never at
import, so every xdist worker collects the same tests and only the worker
that runs this file loads the TPU library. Skips where no TPU compiler is
installed.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.models.transformer import DenseTransformer

CFG = get_config("qwen3-1.7b")
KV = CFG.num_kv_heads
QP = CFG.num_heads // CFG.num_kv_heads
HD = CFG.head_dim
PAGE = 16          # build_real_engine's default block_size
NUM_BLOCKS = 1041  # paged pool chip_smoke.py serves with, scratch block included
# the pool build_real_engine derives under the default BatchLimits (the
# benchmark's), scratch block included
SERVED_BLOCKS = 1281


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


def test_paged_decode_step_writes_kv_in_place_for_v5e(one_chip):
    """The decode step's per-layer K/V write updates the donated stacked pool
    in place: no instruction of the optimized HLO but a parameter or a bitcast
    outputs the pool's full shape (a relayout of the pool shows up as
    ``copy``/``copy_bitcast_fusion`` ops of that shape, one per layer). Four
    layers keep the compile short; at two the pool is small enough to be
    placed in on-chip memory and the HLO takes another form."""
    model = DenseTransformer(dataclasses.replace(CFG, num_layers=4))
    B, NB = 16, 8

    def on_chip(x):
        return _spec(one_chip, x.shape, x.dtype)

    params = jax.tree.map(on_chip, jax.eval_shape(model.init_params,
                                                  jax.random.PRNGKey(0)))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_paged_pools(SERVED_BLOCKS, PAGE)))

    def decode_step(params, pools, tokens, positions, tables, ctx):
        return model.decode_step_paged(params, pools, tokens, positions,
                                       tables, ctx, attn_impl="pallas")

    rows = _spec(one_chip, (B,), jnp.int32)
    hlo = jax.jit(decode_step, donate_argnums=(1,)).lower(
        params, pools, rows, rows, _spec(one_chip, (B, NB), jnp.int32),
        rows).compile().as_text()
    pool_shape = "bf16[%s]" % ",".join(map(str, pools["k"].shape))
    ops = re.findall(r"= %s\{[^}]*\} ([\w-]+)\(" % re.escape(pool_shape), hlo)
    assert ops and "tpu_custom_call" in hlo
    assert set(ops) <= {"parameter", "bitcast"}, sorted(set(ops))
