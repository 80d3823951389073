"""RWKV6 chunked-WKV kernel (TPU Pallas).

One grid step processes one (batch, head) pair's chunk of ``c`` tokens:
cumulative per-channel log-decays, the strictly-lower-triangular decay-weighted
intra-chunk attention matrix A (all exponents <= 0 — numerically safe), the
inter-chunk state contribution, and the state update. The [c, c] products run
on the MXU; the decay reweighting is VPU elementwise work on [c, c, K] tiles
held in VMEM (c=64, K=64 -> 1 MB f32, well within budget).

Layouts: r/k/v/logw [B, c, H, K]; u [H, K]; state [B, H, K, V] (f32).
Outputs: o [B, c, H, V], new_state [B, H, K, V].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s_ref, o_ref, s_out_ref):
    c = r_ref.shape[1]
    f32 = jnp.float32
    r = r_ref[0, :, 0].astype(f32)          # [c, K]
    k = k_ref[0, :, 0].astype(f32)
    v = v_ref[0, :, 0].astype(f32)
    logw = w_ref[0, :, 0].astype(f32)
    u = u_ref[0].astype(f32)                # [K]
    state = s_ref[0, 0].astype(f32)         # [K, V]

    ldi = jnp.cumsum(logw, axis=0)          # inclusive decay log-sums [c, K]
    lde = ldi - logw                        # exclusive

    # inter-chunk: state contribution
    rd = r * jnp.exp(lde)
    o = jax.lax.dot_general(rd, state, (((1,), (0,)), ((), ())))   # [c, V]

    # intra-chunk: A[t, j] = sum_k r[t,k] k[j,k] exp(lde[t,k] - ldi[j,k]), j < t
    diff = lde[:, None, :] - ldi[None, :, :]                        # [c, c, K]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    wdec = jnp.where(tri[:, :, None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * wdec, axis=-1)      # [c, c]
    diag = jnp.sum(r * k * u[None, :], axis=-1)                     # [c]
    A = A + jnp.diag(diag)
    o = o + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())))

    # state update: S' = diag(d_total) S + (k * exp(ldi[-1] - ldi))^T v
    d_total = jnp.exp(ldi[-1])                                      # [K]
    k_scaled = k * jnp.exp(ldi[-1][None, :] - ldi)
    s_new = state * d_total[:, None] + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())))

    o_ref[0, :, 0] = o.astype(o_ref.dtype)
    s_out_ref[0, 0] = s_new.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_chunk(r, k, v, logw, u, state, *, interpret: bool):
    B, c, H, K = r.shape
    V = state.shape[-1]
    grid = (B, H)
    io_spec = pl.BlockSpec((1, c, 1, K), lambda b, h: (b, 0, h, 0))
    out, s_new = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            io_spec, io_spec,
            pl.BlockSpec((1, c, 1, V), lambda b, h: (b, 0, h, 0)),
            io_spec,
            pl.BlockSpec((1, K), lambda b, h: (h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, 1, V), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, c, H, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        interpret=interpret,
    )(r, k, v, logw, u, state)
    return out, s_new
