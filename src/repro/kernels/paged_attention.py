"""Paged-attention decode kernel (TPU Pallas).

One query token per sequence attends a paged KV cache. TPU adaptation of
vLLM's CUDA kernel: the GPU's shared-memory staging becomes explicit HBM→VMEM
BlockSpec tiling; the block table is scalar-prefetched (SMEM) and drives the
page index_map, so each grid step DMAs exactly one [page_size, head_dim] K/V
tile of one kv head. The pool keeps the kv-head axis ahead of the page axis
so that tile is the block's last two dims — Mosaic requires those to be
(8, 128)-divisible or whole, which a [page, KV, hd] layout with one head per
step cannot satisfy.

Layouts (matching the engine's packed-GQA scheme):
  q            [B, KV, Qp, hd]     one token per sequence
  k/v_pages    [P, KV, page, hd]   paged KV pool
  block_tables [B, max_pages]      page ids per sequence (pad with 0)
  context_lens [B]                 valid tokens per sequence
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, cl_ref,           # scalar-prefetch refs
            q_ref, k_ref, v_ref,       # VMEM tiles
            o_ref,
            acc_ref, m_ref, l_ref,     # VMEM scratch
            *, page_size: int, num_pages: int, num_q_tokens: int,
            q_per_token: int):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = cl_ref[b]
    page_start = i * page_size

    @pl.when(page_start < ctx)
    def _step():
        hd = q_ref.shape[-1]
        scale = 1.0 / math.sqrt(hd)
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [Qt*Qp, hd]
        k = k_ref[0, 0].astype(jnp.float32)               # [page, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        rows = q.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # [rows, page]
        tok = page_start + jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 1)
        # causal chunk mask: q row r belongs to query token r // Qp, whose
        # absolute position is ctx - Qt + r // Qp (the chunk's Qt tokens end
        # the context). Qt == 1 degenerates to the classic tok < ctx mask.
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0)
        qpos = ctx - num_q_tokens + row // q_per_token
        s = jnp.where(tok <= qpos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(i == num_pages - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "num_q_tokens"))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    *, interpret: bool, num_q_tokens: int = 1):
    """See module docstring for layouts. ``interpret=True`` runs the kernel
    body through the Pallas interpreter (CPU validation); there is no
    default, so no caller lands in the interpreter by omission.

    ``num_q_tokens`` > 1 runs a *chunk* of query tokens per sequence against
    the paged cache (speculative verify / chunked-prefill continuation): the
    q row axis is then [Qt * Qp] with query token t at absolute position
    ``context_lens[b] - Qt + t``, causally masked inside the kernel.
    """
    B, KV, rows, hd = q.shape
    if rows % num_q_tokens:
        raise ValueError(f"q rows {rows} not divisible by num_q_tokens"
                         f" {num_q_tokens}")
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd), lambda b, h, i, bt, cl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, i, bt, cl: (bt[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, i, bt, cl: (bt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd), lambda b, h, i, bt, cl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, page_size=page_size, num_pages=max_pages,
                               num_q_tokens=num_q_tokens,
                               q_per_token=rows // num_q_tokens)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, q, k_pages, v_pages)
