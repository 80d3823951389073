"""Pure-jnp oracles for every Pallas kernel. Tests sweep shapes/dtypes and
assert_allclose kernel (interpret=True) against these."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q, k_pages, v_pages, block_tables, context_lens,
                        *, num_q_tokens: int = 1):
    """q: [B, KV, Qt*Qp, hd]; k/v_pages: [num_pages, KV, page, hd];
    block_tables: [B, max_pages]; context_lens: [B] -> out [B, KV, Qt*Qp, hd].

    ``num_q_tokens`` (Qt) > 1: a chunk of query tokens per sequence, token t
    at absolute position ``context_lens[b] - Qt + t`` (causally masked) —
    mirrors the Pallas kernel's chunk mode."""
    B, KV, rows, hd = q.shape
    page = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)

    # [B, max_pages, KV, page, hd] -> [B, max_pages * page, KV, hd]
    k = jnp.swapaxes(k_pages[block_tables], 2, 3)
    v = jnp.swapaxes(v_pages[block_tables], 2, 3)
    k = k.reshape(B, max_pages * page, KV, hd)
    v = v.reshape(B, max_pages * page, KV, hd)
    s = jnp.einsum("bgqh,btgh->bgqt", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    idx = jnp.arange(max_pages * page)
    # per-row causal bound: key position must not exceed the row's query
    # token position (== ctx - 1 for every row when Qt == 1)
    qtok = jnp.repeat(jnp.arange(num_q_tokens), rows // num_q_tokens)  # [rows]
    qpos = context_lens[:, None] - num_q_tokens + qtok[None, :]        # [B, rows]
    valid = idx[None, None, :] <= qpos[:, :, None]                     # [B, rows, T]
    s = jnp.where(valid[:, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqt,btgh->bgqh", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def flash_prefill_ref(q, k, v, *, causal=True, q_offset=0, window=0):
    """q: [B, G, S, R, hd] (R = q rows per kv slot); k/v: [B, G, T, hd].
    q row (s, r) attends keys t <= s + q_offset (and within window)."""
    B, G, S, R, hd = q.shape
    T = k.shape[2]
    scale = 1.0 / math.sqrt(hd)
    s_ = jnp.einsum("bgsrh,bgth->bgsrt", q.astype(jnp.float32) * scale,
                    k.astype(jnp.float32))
    qpos = q_offset + jnp.arange(S)
    kpos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    s_ = jnp.where(mask[None, None, :, None, :], s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    o = jnp.einsum("bgsrt,bgth->bgsrh", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def rwkv6_chunk_ref(r, k, v, logw, u, state):
    """Naive sequential recurrence — the gold oracle for the chunked kernel.
    r/k/v/logw: [B, c, H, K]; u: [H, K]; state: [B, H, K, V]."""
    f32 = jnp.float32
    r, k, v, logw = (x.astype(f32) for x in (r, k, v, logw))
    state = state.astype(f32)
    c = r.shape[1]
    outs = []
    for t in range(c):
        kv = k[:, t][..., :, None] * v[:, t][..., None, :]       # [B, H, K, V]
        o = jnp.einsum("bhk,bhkv->bhv", r[:, t], state + u[None, :, :, None] * kv)
        outs.append(o)
        state = state * jnp.exp(logw[:, t])[..., None] + kv
    return jnp.stack(outs, axis=1), state
