"""Kernel parity suite for the paged KV path (CI fast lane, CPU interpret).

``paged_attention`` (Pallas, interpret=True) is pinned against the pure-jnp
oracle over the layouts the paged executor actually produces: fragmented
block tables, physically *shared* prefix blocks between sequences, ragged
context lengths, chunked multi-token queries, and CoW-forked sequences whose
tails diverged after sharing a prefix."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine.kv_cache import BlockManager
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models.transformer import DenseTransformer

KEY = jax.random.PRNGKey(11)


def _rand(shape, dtype, k):
    return jax.random.normal(k, shape).astype(dtype)


def _assert_close(out, ref, dtype):
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def _dense_ref(q, k_seq, v_seq):
    """Straight softmax attention over a contiguous [T, KV, hd] sequence."""
    import math
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("gqh,tgh->gqt", q.astype(jnp.float32) * scale,
                   k_seq.astype(jnp.float32))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("gqt,tgh->gqh", p, v_seq.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fragmented_and_shared_block_tables(dtype):
    """Two sequences share their leading pages (physically identical ids, as
    the prefix-sharing executor allocates them) while the rest of both tables
    is fragmented across the pool in arbitrary order."""
    B, KV, Qp, hd, page, maxp = 2, 2, 2, 32, 8, 6
    P = 24
    ks = jax.random.split(KEY, 3)
    q = _rand((B, KV, Qp, hd), dtype, ks[0])
    kp = _rand((P, KV, page, hd), dtype, ks[1])
    vp = _rand((P, KV, page, hd), dtype, ks[2])
    # shared prefix: both rows reference pages [17, 3]; suffixes fragmented
    bt = np.array([[17, 3, 11, 7, 2, 19],
                   [17, 3, 5, 13, 23, 0]], np.int32)
    cl = np.array([43, 38], np.int32)
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl),
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl))
    _assert_close(out, ref, dtype)
    # the gathered-page computation must equal attention over the contiguous
    # sequence each table describes
    for b in range(B):
        k_seq = jnp.swapaxes(kp[bt[b]], 1, 2).reshape(-1, KV, hd)[: cl[b]]
        v_seq = jnp.swapaxes(vp[bt[b]], 1, 2).reshape(-1, KV, hd)[: cl[b]]
        dense = _dense_ref(q[b], k_seq, v_seq)
        _assert_close(out[b], dense, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seed", [0, 1])
def test_ragged_context_lengths(dtype, seed):
    B, KV, Qp, hd, page, maxp = 4, 2, 3, 64, 16, 5
    P = B * maxp + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand((B, KV, Qp, hd), dtype, ks[0])
    kp = _rand((P, KV, page, hd), dtype, ks[1])
    vp = _rand((P, KV, page, hd), dtype, ks[2])
    rng = np.random.RandomState(seed)
    bt = rng.permutation(P)[: B * maxp].reshape(B, maxp).astype(np.int32)
    # every raggedness regime: 1 token, mid-page, page boundary, full
    cl = np.array([1, page * 2 + 7, page * 3, page * maxp], np.int32)
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl),
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl))
    _assert_close(out, ref, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("num_q_tokens", [2, 4])
def test_chunked_queries(dtype, num_q_tokens):
    """Chunk mode: Qt query tokens per sequence, causally masked inside the
    kernel — token t sees positions <= ctx - Qt + t."""
    B, KV, Qp, hd, page, maxp = 2, 2, 2, 32, 8, 4
    P = 16
    ks = jax.random.split(KEY, 3)
    rows = num_q_tokens * Qp
    q = _rand((B, KV, rows, hd), dtype, ks[0])
    kp = _rand((P, KV, page, hd), dtype, ks[1])
    vp = _rand((P, KV, page, hd), dtype, ks[2])
    rng = np.random.RandomState(3)
    bt = rng.permutation(P)[: B * maxp].reshape(B, maxp).astype(np.int32)
    cl = np.array([page * 2 + 5, page * 4], np.int32)
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl),
                          interpret=True, num_q_tokens=num_q_tokens)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray(cl),
                              num_q_tokens=num_q_tokens)
    _assert_close(out, ref, dtype)
    # chunk causality: query token t must equal a Qt=1 call at ctx - Qt + 1 + t
    for t in range(num_q_tokens):
        qt = q[:, :, t * Qp:(t + 1) * Qp, :]
        cl_t = cl - num_q_tokens + 1 + t
        one = paged_attention_ref(qt, kp, vp, jnp.asarray(bt),
                                  jnp.asarray(cl_t))
        _assert_close(ref[:, :, t * Qp:(t + 1) * Qp, :], one, dtype)


def test_cow_forked_sequences():
    """A forked child shares its parent's pages until its first divergent
    append, which must land in a *private* copy: afterwards parent and child
    attend different tails while the shared prefix stays physically one."""
    page, KV, hd = 4, 2, 16
    bm = BlockManager(num_blocks=16, block_size=page)
    P = bm.num_blocks + 1
    scratch = P - 1

    rng = np.random.RandomState(0)
    kp = rng.randn(P, KV, page, hd).astype(np.float32)
    vp = rng.randn(P, KV, page, hd).astype(np.float32)

    bm.allocate("parent", 6)                      # 2 pages, tail half-full
    child_alloc = bm.fork("parent", "child")
    assert child_alloc.block_ids == bm.block_table("parent")
    assert child_alloc.num_tokens == 6

    # child's first append diverges -> CoW of the shared tail page
    new_blk, copy = bm.append_token_cow("child")
    assert copy is not None, "append into a shared tail must trigger CoW"
    src, dst = copy
    assert new_blk == dst
    assert bm.block_table("parent")[1] == src
    assert bm.block_table("child")[1] == dst
    kp[dst] = kp[src]                             # device-side page clone
    vp[dst] = vp[src]
    # divergent writes: child token 6, then parent token 6 — different values
    kp[dst, :, 2] = 1.0
    vp[dst, :, 2] = 1.0
    _, copy2 = bm.append_token_cow("parent")
    assert copy2 is None, "parent's tail is private after the child's CoW"
    kp[src, :, 2] = -1.0
    vp[src, :, 2] = -1.0
    bm.check_invariants()

    # both sequences now hold 7 tokens; identical prefix, divergent tail
    q = jnp.asarray(rng.randn(2, KV, 1, hd).astype(np.float32))
    q = jnp.concatenate([q[:1], q[:1]])           # same query for both rows
    maxp = 2
    bt = np.full((2, maxp), scratch, np.int32)
    bt[0, :2] = bm.block_table("parent")
    bt[1, :2] = bm.block_table("child")
    cl = np.array([7, 7], np.int32)
    out = paged_attention_ref(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                              jnp.asarray(bt), jnp.asarray(cl))
    pa = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                         jnp.asarray(bt), jnp.asarray(cl), interpret=True)
    _assert_close(pa, out, jnp.float32)
    parent_o, child_o = np.asarray(out[0]), np.asarray(out[1])
    assert not np.allclose(parent_o, child_o), \
        "divergent tails must produce different attention outputs"
    # re-run with the divergent token masked out: identical prefixes agree
    cl6 = np.array([6, 6], np.int32)
    out6 = paged_attention_ref(jnp.asarray(q), jnp.asarray(kp),
                               jnp.asarray(vp), jnp.asarray(bt),
                               jnp.asarray(cl6))
    np.testing.assert_allclose(np.asarray(out6[0]), np.asarray(out6[1]),
                               rtol=1e-6, atol=1e-6)

    bm.free("parent")
    bm.free("child")
    bm.check_invariants()
    assert bm.free_blocks == bm.num_blocks


def test_fork_conservation_under_churn():
    """fork/append/free churn never violates block conservation and CoW never
    lets two live sequences write the same page."""
    bm = BlockManager(num_blocks=64, block_size=4)
    bm.allocate("a", 10)
    bm.fork("a", "b")
    bm.fork("a", "c")
    writers = {}
    for seq in ("a", "b", "c"):
        for _ in range(6):
            bid, copy = bm.append_token_cow(seq)
            write_blk = bm.block_table(seq)[(bm.context_len(seq) - 1)
                                            // bm.block_size]
            owner = writers.get(write_blk)
            assert owner in (None, seq), \
                f"block {write_blk} written by {owner} and {seq}"
            writers[write_blk] = seq
            bm.check_invariants()
    bm.free("b")
    bm.check_invariants()
    bm.free("a")
    bm.free("c")
    bm.check_invariants()
    assert bm.free_blocks == bm.num_blocks


@pytest.mark.parametrize("batch", [1, 5, 16])
def test_decode_kv_write_matches_plain_indexing(batch):
    """``write_token_kv`` leaves the stacked pool exactly as the plain
    ``.at[g, i, bids, :, offs].set(kv)`` does, for random K/V, ragged positions
    and fragmented block tables. Pad rows (the second half of the batch) all
    write slot 0 of the scratch block, where which duplicate wins is
    unspecified, so every block but that one is compared."""
    G, NF, NB, KV, page, hd, per_seq = 3, 2, 41, 2, 4, 8, 2
    scratch = NB - 1
    live = max(1, batch // 2)
    rng = np.random.RandomState(batch)
    ks = jax.random.split(jax.random.PRNGKey(batch), 2)
    pool = _rand((G, NF, NB, KV, page, hd), jnp.bfloat16, ks[0])
    kv = _rand((batch, KV, hd), jnp.float32, ks[1])
    tables = np.full((batch, per_seq), scratch, np.int32)
    tables[:live] = rng.permutation(scratch)[: live * per_seq].reshape(
        live, per_seq)
    positions = np.zeros((batch,), np.int32)
    positions[:live] = rng.randint(0, per_seq * page, size=live)
    bids = jnp.asarray(tables[np.arange(batch), positions // page])
    offs = jnp.asarray(positions % page)
    for g, i in [(0, 0), (G - 1, NF - 1)]:
        got = DenseTransformer.write_token_kv(pool, g, i, bids, offs, kv)
        want = pool.at[g, i, bids, :, offs].set(kv.astype(pool.dtype))
        np.testing.assert_array_equal(np.asarray(got[:, :, :scratch]),
                                      np.asarray(want[:, :, :scratch]))
        assert not np.array_equal(np.asarray(got[g, i, :scratch]),
                                  np.asarray(pool[g, i, :scratch]))
