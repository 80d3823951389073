#!/usr/bin/env python3
"""On-chip smoke run: serve Qwen3-1.7B at published widths on one TPU.

Drives the real serving path (Frontend -> EngineCore -> real executor ->
Pallas kernels) once on the paged KV backend and once on the dense one, with
random bf16 weights from ``--seed``, then checks the kernels against their
references at full width:

  * one paged decode step through the compiled ``paged_attention`` kernel
    against the same step with ``attn_impl="ref"`` on identical pools;
  * one full-width ``paged_attention`` call against its jnp oracle;
  * prefill logits through ``flash_prefill`` against block-attention prefill.

    python chip_smoke.py [--seed N]

Needs a TPU: without one it exits non-zero before any work and prints no
result. Every reading printed before the last line is a smoke reading (one
small trace, cold compiles), not a benchmark. The last line is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed check raises, so the run exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

ARCH = "qwen3-1.7b"
MAX_LEN = 512          # per-sequence KV capacity: prompt (< 256) + 16 outputs
NUM_RELQUERIES = 4
ROWS = 4
OUTPUT_TOKENS = 16
DENSE_SLOTS = 16       # every request of the trace resident at once

# bf16 agreement bounds. The kernel keeps softmax weights in f32 where the
# jnp reference rounds them to bf16, so each layer's attention output moves
# by about one bf16 ulp (2^-8 relative). Through 28 layers of random weights
# that noise reaches 4.5-4.8% of the largest logit on a TPU v5e at seed 0,
# close to LOGIT_TOL; the single attention call, held to ATTN_TOL, is the
# sharp check. A wrong mask, page or head shows up as an O(1) error in both.
LOGIT_TOL = 5e-2       # max |kernel - ref| / max |ref| over the logits
ATTN_TOL = 2e-2        # atol = rtol for one attention call (tests' bf16 bound)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def reading(name: str, value) -> None:
    print(f"[smoke reading] {name}: {value}")


def build_trace(cfg, seed: int):
    """4 relQueries x 4 rows, 16 output tokens each (EOS may end a row
    earlier). Built fresh per backend: serving fills in the requests."""
    from repro.data.datasets import make_dataset
    from repro.data.trace import TraceConfig, build_trace as _build
    from repro.engine.tokenizer import HashTokenizer

    tok = HashTokenizer(vocab_size=cfg.vocab_size - 2)
    ds = make_dataset("rotten", num_rows=1000, seed=seed)
    return _build(ds, TraceConfig(
        num_relqueries=NUM_RELQUERIES, rate=100.0, seed=seed,
        min_requests=ROWS, max_requests=ROWS,
        output_token_cap=OUTPUT_TOKENS), tokenizer=tok)


def serve_trace(model, params, trace, kv_backend: str, **engine_kw):
    """Serve ``trace`` through ``Frontend(build_real_engine(...))``; returns
    (engine, {(rel_id, row): streamed tokens}). Raises unless every relQuery
    finished and every request streamed exactly its output tokens."""
    from repro.core.priority import BatchLimits
    from repro.serving import Frontend, build_real_engine
    from repro.serving.frontend import RelQueryStatus

    engine = build_real_engine(
        ARCH, "relserve", kv_backend, model=model, params=params,
        limits=BatchLimits(max_num_seqs=DENSE_SLOTS), max_len=MAX_LEN,
        **engine_kw)
    streamed = {}
    fe = Frontend(engine)
    fe.replay(trace, on_token=lambda rid, t: streamed.setdefault(rid, []).append(t))
    for rq in trace:
        status = fe.handles[rq.rel_id].status()
        if status is not RelQueryStatus.FINISHED:
            raise AssertionError(f"{kv_backend}: relQuery {rq.rel_id} {status}")
        for r in rq.requests:
            got = streamed.get(r.req_id, [])
            if not got or got != list(r.output_tokens):
                raise AssertionError(
                    f"{kv_backend}: {r.req_id} streamed {len(got)} tokens, "
                    f"produced {len(r.output_tokens)}")
    fe.close()
    return engine, {(rq.rel_id, i): streamed[r.req_id]
                    for rq in trace for i, r in enumerate(rq.requests)}


def executor_readings(tag: str, ex) -> None:
    reading(f"{tag} compiles", f"{ex.compile_count} in {ex.compile_s_total:.1f} s")
    pre = [s for _, s in ex.prefill_samples]
    dec = [s for _, s in ex.decode_samples]
    reading(f"{tag} prefill batch s (median of {len(pre)})", statistics.median(pre))
    reading(f"{tag} decode step s (median of {len(dec)})", statistics.median(dec))


def agreement(a, b) -> str:
    """Position-wise token agreement of two {(rel_id, row): tokens} maps."""
    same = sum(x == y for k in a for x, y in zip(a[k], b[k]))
    total = sum(max(len(a[k]), len(b[k])) for k in a)
    return (f"{same}/{total} tokens, "
            f"{sum(a[k] == b[k] for k in a)}/{len(a)} streams")


def decode_step_err(model, params, seed: int) -> float:
    """One paged decode step, compiled kernel vs jnp reference on identical
    pools; returns max |kernel - ref| / max |ref| over the logits."""
    import jax
    import jax.numpy as jnp

    pools, tables, ctx = random_pools(model, seed)
    rng = jax.random.PRNGKey(seed + 2)
    tokens = jax.random.randint(rng, ctx.shape, 0, model.cfg.vocab_size)
    step = jax.jit(model.decode_step_paged, static_argnames=("attn_impl",))
    args = (params, pools, tokens, ctx - 1, tables, ctx)
    got, _ = step(*args, attn_impl="pallas")
    want, _ = step(*args, attn_impl="ref")
    return compare_logits("paged decode step (pallas vs ref)", got, want)


def random_pools(model, seed: int):
    """N(0, 1) K/V pools of 65 blocks, and 4 sequences of 16 fragmented
    pages with contexts of 1 token, mid-page, 200 tokens and full."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    B, NB, bs, P = 4, 16, 16, 65
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    shapes = jax.eval_shape(lambda: model.init_paged_pools(P, bs))
    pools = {n: jax.random.normal(k, shapes[n].shape, jnp.float32).astype(
        shapes[n].dtype) for n, k in (("k", k1), ("v", k2))}
    perm = np.random.RandomState(seed).permutation(P - 1)
    tables = jnp.asarray(perm[:B * NB].reshape(B, NB), jnp.int32)
    ctx = jnp.asarray([1, 17, 200, NB * bs], jnp.int32)
    return pools, tables, ctx


def check_paged_attention(model, seed: int) -> None:
    """One full-width attention call against the kernel's own oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref

    pools, tables, ctx = random_pools(model, seed)
    k, v = pools["k"][0, 0], pools["v"][0, 0]
    q = jax.random.normal(jax.random.PRNGKey(seed + 3),
                          (ctx.shape[0], model.layout.kv_slots,
                           model.layout.q_per_slot, model.cfg.head_dim),
                          jnp.float32).astype(k.dtype)
    o = np.asarray(paged_attention(q, k, v, tables, ctx, interpret=False),
                   np.float32)
    o_ref = np.asarray(paged_attention_ref(q, k, v, tables, ctx), np.float32)
    reading("paged_attention vs oracle max abs err",
            float(np.max(np.abs(o - o_ref))))
    np.testing.assert_allclose(o, o_ref, atol=ATTN_TOL, rtol=ATTN_TOL)


def check_flash_prefill(model, params, seed: int) -> None:
    """Prefill logits through flash_prefill vs block attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed + 1)
    L = 256
    toks = jnp.asarray(rng.randint(0, model.cfg.vocab_size, (2, L)), jnp.int32)
    seq_lens = jnp.asarray([L, 200], jnp.int32)
    flash = model.with_prefill_attn("flash")

    def logits(m):
        return jax.jit(lambda p, t, sl: m.prefill(p, t, seq_lens=sl,
                                                  max_len=L)[0])(
            params, toks, seq_lens)

    err = compare_logits("prefill (flash vs block)", logits(flash),
                         logits(model))
    if err > LOGIT_TOL:
        raise AssertionError(f"flash prefill logits differ by {err:.3g} > {LOGIT_TOL}")


def compare_logits(name: str, got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    reading(f"{name} max err / max |logit|", err)
    reading(f"{name} greedy agreement", agree)
    return err


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"no RelServe sources at {SRC}; run from a checkout of the repo")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found no TPU (platform {dev.platform!r}, "
             f"{len(jax.devices())} device(s))")
    sys.path.insert(0, str(SRC))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.registry import build_model

    reading("device", f"{dev.device_kind} x{len(jax.devices())}")
    reading("compile cache", use_compile_cache())
    cfg = get_config(ARCH)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init_params)(jax.random.PRNGKey(args.seed)))
    reading("params", f"{model.param_count() / 1e9:.3f} B {cfg.dtype}, "
                      f"init {time.perf_counter() - t0:.1f} s")

    # paged backend: Pallas paged_attention decode + flash_prefill prefill
    t0 = time.perf_counter()
    engine, paged = serve_trace(model, params, build_trace(cfg, args.seed),
                                "paged")
    ex = engine.core.executor
    reading("paged serve wall s (compiles included)", time.perf_counter() - t0)
    if ex.attn_impl != "pallas":
        raise AssertionError(f"paged decode ran attn_impl={ex.attn_impl!r}")
    if ex.model.prefill_attn_impl != "flash":
        raise AssertionError(f"paged prefill ran {ex.model.prefill_attn_impl!r}")
    for kind, fns in (("decode", ex._decode_fn), ("prefill", ex._prefill_fn)):
        for key, exe in fns.items():
            if "tpu_custom_call" not in exe.as_text():
                raise AssertionError(f"{kind} program {key} has no Pallas kernel")
    if ex.bm.free_blocks != ex.bm.num_blocks:
        raise AssertionError(f"paged pool not drained: {ex.bm.free_blocks} of "
                             f"{ex.bm.num_blocks} blocks free")
    executor_readings("paged", ex)
    del engine, ex
    gc.collect()

    # dense backend at a slot count that fits next to the weights
    t0 = time.perf_counter()
    engine, dense = serve_trace(model, params, build_trace(cfg, args.seed),
                                "dense", max_slots=DENSE_SLOTS)
    ex = engine.core.executor
    reading("dense serve wall s (compiles included)", time.perf_counter() - t0)
    if any(s is not None for s in ex.slots):
        raise AssertionError("dense slots not released")
    executor_readings("dense", ex)
    del engine, ex
    gc.collect()

    reading("dense vs paged greedy agreement", agreement(paged, dense))

    err = decode_step_err(model, params, args.seed)
    if err > LOGIT_TOL:
        raise AssertionError(f"paged decode logits differ by {err:.3g} > {LOGIT_TOL}")
    check_paged_attention(model, args.seed)
    check_flash_prefill(model, params, args.seed)

    stats = dev.memory_stats() or {}
    reading("peak HBM GB", stats.get("peak_bytes_in_use", 0) / 1e9)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
