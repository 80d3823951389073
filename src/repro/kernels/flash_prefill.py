"""Flash-attention prefill kernel (TPU Pallas) with prefix-cache offset and
sliding-window support.

The GQA-packed layout folds the Qp q-rows of each kv slot into the q tile's
row dimension, so the MXU sees [q_block*Qp, hd] x [hd, kv_block] matmuls.
Causality works on the *sequence* index (row // Qp) shifted by ``q_offset`` —
this is what lets a prefix-cached prefill attend the cached tokens without
recomputing them (paper Fig. 7's utok linearity).

Layouts:
  q [B, G, S, R, hd]  (G = kv slots, R = q rows per slot)
  k [B, G, T, hd], v [B, G, T, hd]; T >= q_offset + S
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, q_block: int, kv_block: int, rows: int, num_kv: int,
            q_offset: int, causal: bool, window: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    hd = q_ref.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    q = q_ref[0].astype(jnp.float32) * scale                 # [q_block*R, hd]
    k = k_ref[0].astype(jnp.float32)                         # [kv_block, hd]
    v = v_ref[0].astype(jnp.float32)
    n_rows = q.shape[0]

    # absolute positions: q row r belongs to sequence index (qi*qb + r//R)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_rows, kv_block), 0)
    qpos = q_offset + qi * q_block + row // rows
    kpos = kj * kv_block + jax.lax.broadcasted_iota(jnp.int32, (n_rows, kv_block), 1)
    mask = jnp.ones((n_rows, kv_block), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [rows, kv_block]
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(kj == num_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "q_block", "kv_block", "interpret"))
def flash_prefill(q, k, v, *, causal: bool = True, window: int = 0,
                  q_offset: int = 0, q_block: int = 128, kv_block: int = 128,
                  interpret: bool):
    """See module docstring for layouts. ``interpret=True`` runs the kernel
    body through the Pallas interpreter (CPU validation); there is no
    default, so no caller lands in the interpreter by omission."""
    B, G, S, R, hd = q.shape
    T = k.shape[2]
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    assert S % q_block == 0 and T % kv_block == 0
    nq, nk = S // q_block, T // kv_block
    q2 = q.reshape(B, G, S * R, hd)

    grid = (B * G, nq, nk)
    kernel = functools.partial(
        _kernel, q_block=q_block, kv_block=kv_block, rows=R, num_kv=nk,
        q_offset=q_offset, causal=causal, window=window)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block * R, hd), lambda bg, i, j: (bg, i, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda bg, i, j: (bg, j, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda bg, i, j: (bg, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block * R, hd), lambda bg, i, j: (bg, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * G, S * R, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block * R, hd), jnp.float32),
            pltpu.VMEM((q_block * R, 1), jnp.float32),
            pltpu.VMEM((q_block * R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q2.reshape(B * G, S * R, hd), k.reshape(B * G, T, hd),
      v.reshape(B * G, T, hd))
    return out.reshape(B, G, S, R, hd)
