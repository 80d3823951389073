"""Pallas kernels vs pure-jnp oracles (interpret=True), sweeping shapes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import (
    flash_prefill_ref, paged_attention_ref, rwkv6_chunk_ref,
)
from repro.kernels.rwkv6_chunk import rwkv6_chunk

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KV,Qp,hd,page,maxp", [
    (2, 2, 1, 32, 8, 4),
    (4, 2, 3, 64, 16, 6),
    (1, 4, 2, 128, 16, 3),
])
def test_paged_attention(B, KV, Qp, hd, page, maxp, dtype):
    P = B * maxp + 2
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, KV, Qp, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, KV, page, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, KV, page, hd)).astype(dtype)
    rng = np.random.RandomState(0)
    bt = rng.permutation(P)[: B * maxp].reshape(B, maxp).astype(np.int32)
    cl = rng.randint(1, page * maxp + 1, size=(B,)).astype(np.int32)
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl), interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,G,S,R,hd,T,causal,window,qoff", [
    (2, 2, 64, 2, 32, 64, True, 0, 0),
    (1, 3, 128, 1, 64, 128, True, 0, 0),
    (2, 2, 64, 2, 32, 64, True, 16, 0),     # sliding window
    (1, 2, 32, 3, 64, 96, True, 0, 64),     # prefix-cache offset
    (2, 1, 64, 1, 32, 64, False, 0, 0),     # non-causal (whisper encoder)
])
def test_flash_prefill(B, G, S, R, hd, T, causal, window, qoff, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, G, S, R, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, G, T, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, G, T, hd)).astype(dtype)
    out = flash_prefill(q, k, v, causal=causal, window=window, q_offset=qoff,
                        q_block=32, kv_block=32, interpret=True)
    ref = flash_prefill_ref(q, k, v, causal=causal, window=window, q_offset=qoff)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,c,H,K", [(2, 16, 2, 16), (1, 32, 4, 32), (2, 64, 2, 64)])
def test_rwkv6_chunk(B, c, H, K):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, c, H, K))
    k = jax.random.normal(ks[1], (B, c, H, K))
    v = jax.random.normal(ks[2], (B, c, H, K))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, c, H, K)) * 0.5)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, K, K))
    o, s = rwkv6_chunk(r, k, v, logw, u, s0, interpret=True)
    o_ref, s_ref = rwkv6_chunk_ref(r, k, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=5e-4, rtol=5e-4)


def test_rwkv6_chunk_chain_matches_long_recurrence():
    """Chaining chunk kernels across a sequence == one long recurrence."""
    B, c, H, K, nchunks = 1, 16, 2, 16, 4
    ks = jax.random.split(KEY, 5)
    T = c * nchunks
    r = jax.random.normal(ks[0], (B, T, H, K))
    k = jax.random.normal(ks[1], (B, T, H, K))
    v = jax.random.normal(ks[2], (B, T, H, K))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.5)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s = jnp.zeros((B, H, K, K))
    outs = []
    for i in range(nchunks):
        sl = slice(i * c, (i + 1) * c)
        o, s = rwkv6_chunk(r[:, sl], k[:, sl], v[:, sl], logw[:, sl], u, s,
                           interpret=True)
        outs.append(o)
    o_all = jnp.concatenate(outs, axis=1)
    o_ref, s_ref = rwkv6_chunk_ref(r, k, v, logw, u, jnp.zeros((B, H, K, K)))
    np.testing.assert_allclose(np.asarray(o_all), np.asarray(o_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               atol=1e-3, rtol=1e-3)
