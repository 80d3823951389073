"""Serial vs pipelined engine-loop equivalence suite.

The pipelined loop (``EngineCore(engine_loop="pipelined")``) overlaps
scheduling with device compute: batch N is dispatched, then batch N+1 is
planned against a speculatively-completed ledger while N "runs", and the
speculation is committed or rolled back when the wait lands. None of that
may be observable from outside the engine:

- **bit-identical token streams** — every request generates exactly the
  serial loop's tokens, across every policy × admission mode × sharing
  setting, including preemption-heavy configurations;
- **bit-identical reports** — simulated-clock latencies, waiting/core/tail
  breakdowns and the full batch event stream match the serial run;
- **flush on observation** — cancel / submit / snapshot between ticks see
  the exact serial state even with a speculative window open;
- **ledger conservation** — after a drain every KV ledger is zero, same as
  the serial invariants in test_scheduler_metamorphic.py;
- **real executors too** — the dense and paged JAX backends produce
  identical streams and event tuples under either loop (slow lane).

The suite also pins that the pipelining actually engages (nonzero
``overlap_hidden_time``) and that the incremental DynamicPriorityUpdater
refresh changes no priority decision.
"""
import copy

import pytest

from repro.core.latency_model import a100_opt13b
from repro.core.policies import SCHEDULERS
from repro.core.priority import BatchLimits, DPUConfig
from repro.data.datasets import make_dataset
from repro.data.trace import TraceConfig, build_trace
from repro.engine.engine import ServiceReport, ServingEngine, merge_reports
from repro.engine.prefix_cache import PrefixCache
from repro.engine.simulator import SimulatedExecutor, expected_stream
from repro.serving.frontend import Frontend

POLICIES = tuple(SCHEDULERS)
MODES = ("conservative", "optimistic")
LOOPS = ("serial", "pipelined")


def _trace(seed, num_relqueries=8, rate=3.0, max_requests=10):
    ds = make_dataset("rotten", num_rows=2000, seed=seed)
    return build_trace(ds, TraceConfig(
        num_relqueries=num_relqueries, rate=rate, seed=seed,
        max_requests=max_requests, num_templates=2))


def _cap_for(trace, slack=2.0):
    max_fp = max(r.num_prompt_tokens + r.max_output_tokens
                 for rq in trace for r in rq.requests)
    return int(max_fp * slack)


def _build(policy, mode, trace, *, loop, prefix_sharing=False, slack=2.0,
           dpu_config=None, exec_seed=0, tiering=False):
    lm = a100_opt13b()
    pc = PrefixCache(block_size=16)
    cap = _cap_for(trace, slack=slack)
    kw = dict(limits=BatchLimits(cap=cap),
              latency_model=lm, prefix_cache=pc, kv_admission=mode,
              prefix_sharing=prefix_sharing)
    if tiering:
        kw.update(kv_tiering=True, host_kv_cap=8 * cap)
    if policy.startswith("relserve"):
        kw["dpu_config"] = dpu_config or DPUConfig(exact_probe=prefix_sharing)
    sched = SCHEDULERS[policy](**kw)
    engine = ServingEngine(sched, SimulatedExecutor(lm, prefix_cache=pc,
                                                    seed=exec_seed),
                           engine_loop=loop)
    return engine, sched


def _run(policy, mode, trace, *, loop, prefix_sharing=False, slack=2.0,
         dpu_config=None, tiering=False):
    trace = copy.deepcopy(trace)
    engine, sched = _build(policy, mode, trace, loop=loop,
                           prefix_sharing=prefix_sharing, slack=slack,
                           dpu_config=dpu_config, tiering=tiering)
    report = engine.run_trace(trace)
    return report, sched, trace


def _streams(trace):
    return {r.req_id: tuple(r.output_tokens)
            for rq in trace for r in rq.requests}


def _expected_stream(r):
    return expected_stream(r)


def _events(report):
    return [(e.kind, e.start, e.end, e.num_requests, e.uncached_tokens,
             e.rel_ids) for e in report.events]


def _assert_conserved(sched):
    assert sched.tokens_in_use == 0, "tokens_in_use leaked"
    assert sched.committed_tokens == 0, "committed_tokens leaked"
    assert sched.partial_prefill_tokens == 0, "partial chunk ledger leaked"
    if sched._shared_ledger is not None:
        assert sched._shared_ledger.discount == 0, "shared discount leaked"
        assert len(sched._shared_ledger) == 0, "shared ledger holds chains"


def _assert_reports_match(rep_s, rep_p):
    assert rep_s.latencies == rep_p.latencies
    assert rep_s.waiting == rep_p.waiting
    assert rep_s.core == rep_p.core
    assert rep_s.tail == rep_p.tail
    assert _events(rep_s) == _events(rep_p)
    assert rep_s.preemptions == rep_p.preemptions
    assert rep_s.cancelled_rel_ids == rep_p.cancelled_rel_ids


# --------------------------------------------------------------- sim clock
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("policy", POLICIES)
def test_pipelined_matches_serial(policy, mode):
    """Every policy × admission mode: identical streams, latencies and
    batch event tuples on the simulated clock, with conserved ledgers."""
    trace = _trace(seed=3)
    rep_s, _, ran_s = _run(policy, mode, trace, loop="serial")
    rep_p, sched_p, ran_p = _run(policy, mode, trace, loop="pipelined")
    assert _streams(ran_s) == _streams(ran_p)
    _assert_reports_match(rep_s, rep_p)
    _assert_conserved(sched_p)
    for rq in ran_p:
        for r in rq.requests:
            assert r.output_tokens == _expected_stream(r)


@pytest.mark.parametrize("policy", POLICIES)
def test_pipelined_matches_serial_with_sharing(policy):
    """Prefix-sharing-aware scheduling under the pipelined loop: the shared
    ledger replay/rollback must stay exact."""
    trace = _trace(seed=7)
    rep_s, _, ran_s = _run(policy, "optimistic", trace, loop="serial",
                           prefix_sharing=True)
    rep_p, sched_p, ran_p = _run(policy, "optimistic", trace,
                                 loop="pipelined", prefix_sharing=True)
    assert _streams(ran_s) == _streams(ran_p)
    _assert_reports_match(rep_s, rep_p)
    _assert_conserved(sched_p)


@pytest.mark.parametrize("mode", MODES)
def test_pipelined_same_seed_identical_events(mode):
    trace = _trace(seed=5)
    rep_a, _, _ = _run("relserve", mode, trace, loop="pipelined")
    rep_b, _, _ = _run("relserve", mode, trace, loop="pipelined")
    assert _events(rep_a) == _events(rep_b)


def test_pipelined_preemption_heavy_equivalence():
    """A cap tight enough to force hundreds of preempt/re-prefill cycles:
    speculative completion + rollback across victim selection must not
    diverge from serial by a single token or event."""
    trace = _trace(seed=13, num_relqueries=10, rate=6.0, max_requests=12)
    rep_s, _, ran_s = _run("relserve", "optimistic", trace, loop="serial",
                           prefix_sharing=True, slack=1.3,
                           dpu_config=DPUConfig(exact_probe=True))
    rep_p, sched_p, ran_p = _run("relserve", "optimistic", trace,
                                 loop="pipelined", prefix_sharing=True,
                                 slack=1.3,
                                 dpu_config=DPUConfig(exact_probe=True))
    assert rep_s.preemptions > 0, "cap not tight enough to exercise preemption"
    assert _streams(ran_s) == _streams(ran_p)
    _assert_reports_match(rep_s, rep_p)
    _assert_conserved(sched_p)


@pytest.mark.parametrize("policy", POLICIES)
def test_pipelined_tiering_matches_serial(policy):
    """KV tiering under the pipelined loop: swap decisions journaled while a
    batch is in flight (speculative planning) must commit or roll back to
    the exact serial behavior — same streams, same event timing (serial and
    pipelined both charge the modeled swap seconds to the deciding tick),
    same swap counters, host ledger drained."""
    trace = _trace(seed=13, num_relqueries=10, rate=6.0, max_requests=12)
    rep_s, sched_s, ran_s = _run(policy, "optimistic", trace, loop="serial",
                                 slack=1.2, tiering=True)
    rep_p, sched_p, ran_p = _run(policy, "optimistic", trace,
                                 loop="pipelined", slack=1.2, tiering=True)
    if policy in ("relserve", "vllm"):
        assert sched_s.swap_outs > 0, "cap not tight enough to swap"
    assert _streams(ran_s) == _streams(ran_p)
    _assert_reports_match(rep_s, rep_p)
    assert (sched_s.swap_outs, sched_s.swap_ins, sched_s.swap_bytes_moved) \
        == (sched_p.swap_outs, sched_p.swap_ins, sched_p.swap_bytes_moved)
    assert sched_p.host_tokens_in_use == 0
    _assert_conserved(sched_p)


def test_pipelined_tiering_predicted_matches_serial():
    """Predicted admission + tiering: the predictor's speculative-observation
    journal and the swap-op journal roll back together."""
    trace = _trace(seed=5, num_relqueries=10, rate=5.0, max_requests=12)
    rep_s, sched_s, ran_s = _run("relserve", "predicted", trace,
                                 loop="serial", slack=1.3, tiering=True)
    rep_p, sched_p, ran_p = _run("relserve", "predicted", trace,
                                 loop="pipelined", slack=1.3, tiering=True)
    assert _streams(ran_s) == _streams(ran_p)
    _assert_reports_match(rep_s, rep_p)
    assert sched_s.predictor.observations == sched_p.predictor.observations
    _assert_conserved(sched_p)


def test_cancel_with_tiering_pipelined_matches_serial():
    """Cancel between ticks with tiering on and a speculative window open:
    swapped/parked requests drain to the identical serial state."""
    trace = _trace(seed=11, num_relqueries=6, rate=4.0, max_requests=8)

    def script(loop):
        ran = copy.deepcopy(trace)
        engine, sched = _build("relserve", "optimistic", ran, loop=loop,
                               slack=1.2, tiering=True)
        fe = Frontend(engine)
        try:
            handles = [fe.submit(rq, now=rq.arrival_time) for rq in ran]
            for _ in range(4):
                fe.step()
            fe.cancel(handles[2])
            final = fe.drain()
        finally:
            fe.close()
        return _streams(ran), final, sched

    st_s, fin_s, sched_s = script("serial")
    st_p, fin_p, sched_p = script("pipelined")
    assert sched_s.swap_outs > 0, "tiering never engaged in the script"
    assert st_s == st_p
    _assert_reports_match(fin_s, fin_p)
    assert sched_s.host_tokens_in_use == 0 and sched_p.host_tokens_in_use == 0
    _assert_conserved(sched_s)
    _assert_conserved(sched_p)


def test_pipelined_actually_overlaps():
    """Guard against the pipelined loop silently degrading to serial: on a
    policy eligible for speculation the engine must report scheduler time
    hidden behind (simulated) device compute."""
    trace = _trace(seed=3)
    rep, _, _ = _run("relserve", "conservative", trace, loop="pipelined")
    assert rep.overlap_hidden_time > 0.0, "speculation never engaged"


def test_unknown_engine_loop_rejected():
    trace = _trace(seed=3, num_relqueries=2, max_requests=2)
    with pytest.raises(ValueError):
        _build("relserve", "conservative", trace, loop="warp-speed")


# ----------------------------------------------------- frontend interleaving
def _scripted(loop, trace, cancel_after, cancel_idx):
    """Submit everything up front, step ``cancel_after`` batches, cancel one
    relQuery mid-flight, snapshot, then drain — the same script on either
    loop. Returns (streams, mid_report, final_report, sched, trace)."""
    trace = copy.deepcopy(trace)
    engine, sched = _build("relserve", "optimistic", trace, loop=loop,
                           prefix_sharing=True)
    fe = Frontend(engine)
    try:
        handles = [fe.submit(rq, now=rq.arrival_time) for rq in trace]
        for _ in range(cancel_after):
            fe.step()
        fe.cancel(handles[cancel_idx % len(handles)])
        mid = fe.snapshot()
        final = fe.drain()
    finally:
        fe.close()
    return _streams(trace), mid, final, sched, trace


@pytest.mark.parametrize("cancel_after,cancel_idx", [(0, 0), (3, 2), (7, 5)])
def test_cancel_while_in_flight_matches_serial(cancel_after, cancel_idx):
    """Cancelling between ticks with a speculative window open must flush to
    the exact serial state: same surviving streams, same cancelled set, same
    mid-flight snapshot, zeroed ledgers."""
    trace = _trace(seed=11, num_relqueries=6, rate=4.0, max_requests=8)
    st_s, mid_s, fin_s, sched_s, _ = _scripted("serial", trace,
                                               cancel_after, cancel_idx)
    st_p, mid_p, fin_p, sched_p, _ = _scripted("pipelined", trace,
                                               cancel_after, cancel_idx)
    assert st_s == st_p
    assert mid_s.latencies == mid_p.latencies
    assert mid_s.cancelled_rel_ids == mid_p.cancelled_rel_ids
    _assert_reports_match(fin_s, fin_p)
    _assert_conserved(sched_s)
    _assert_conserved(sched_p)


def test_snapshot_mid_flight_sees_no_placeholders():
    """A snapshot taken while a plan is staged must never observe the
    speculative sentinel values (negative tokens, -inf timestamps)."""
    trace = _trace(seed=9, num_relqueries=5, max_requests=6)
    ran = copy.deepcopy(trace)
    engine, _ = _build("relserve", "conservative", ran, loop="pipelined")
    fe = Frontend(engine)
    try:
        for rq in ran:
            fe.submit(rq, now=rq.arrival_time)
        steps = 0
        while fe.step() is not None:
            steps += 1
            rep = fe.snapshot()
            for v in rep.latencies.values():
                assert v == v and v != float("-inf")   # not NaN, not sentinel
            for rq in ran:
                for r in rq.requests:
                    assert all(t >= 0 for t in r.output_tokens), \
                        "speculative placeholder token leaked to a snapshot"
            if steps > 10_000:
                pytest.fail("drain did not terminate")
    finally:
        fe.close()


# --------------------------------------------------- incremental DPU refresh
def test_incremental_dpu_changes_no_decision():
    """Phase-memoized DPU refresh must reproduce the full-rescan run bit for
    bit — same events, same streams, same priority stats — while actually
    serving probes from the memo."""
    trace = _trace(seed=17, num_relqueries=10, rate=4.0)
    rep_full, sched_full, ran_full = _run(
        "relserve", "optimistic", trace, loop="serial",
        dpu_config=DPUConfig(incremental=False))
    rep_inc, sched_inc, ran_inc = _run(
        "relserve", "optimistic", trace, loop="serial",
        dpu_config=DPUConfig(incremental=True))
    assert _streams(ran_full) == _streams(ran_inc)
    assert _events(rep_full) == _events(rep_inc)
    assert rep_full.latencies == rep_inc.latencies
    assert sched_inc.dpu.stats["phase_memo_hits"] > 0, "memo never used"
    # the non-incremental path never consults (or populates) the memo
    assert sched_full.dpu.stats["phase_probes"] == 0
    assert sched_full.dpu.stats["phase_memo_hits"] == 0


def test_incremental_dpu_identical_under_pipelined():
    """Memo versioning must survive checkpoint/rollback: a pipelined run
    with incremental refresh still matches serial-full-rescan exactly."""
    trace = _trace(seed=17, num_relqueries=10, rate=4.0)
    rep_full, _, ran_full = _run("relserve", "optimistic", trace,
                                 loop="serial",
                                 dpu_config=DPUConfig(incremental=False))
    rep_inc, sched_p, ran_inc = _run("relserve", "optimistic", trace,
                                     loop="pipelined",
                                     dpu_config=DPUConfig(incremental=True))
    assert _streams(ran_full) == _streams(ran_inc)
    assert _events(rep_full) == _events(rep_inc)
    _assert_conserved(sched_p)


# ------------------------------------------------------- report plumbing
def test_report_merge_sums_pipeline_counters():
    a = ServiceReport(latencies={"a": 1.0}, waiting={}, core={}, tail={},
                      events=[], end_to_end=1.0,
                      schedule_retry_time=0.25, overlap_hidden_time=1.5,
                      schedule_retries=3)
    b = ServiceReport(latencies={"b": 2.0}, waiting={}, core={}, tail={},
                      events=[], end_to_end=2.0,
                      schedule_retry_time=0.5, overlap_hidden_time=0.75,
                      schedule_retries=2)
    merged = merge_reports([a, b])
    assert merged.schedule_retry_time == pytest.approx(0.75)
    assert merged.overlap_hidden_time == pytest.approx(2.25)
    assert merged.schedule_retries == 5


# --------------------------------------------------------- real executors
def _real_fixture(model_cache={}):
    """Shared smoke model/params plus ONE canonical trace (deepcopied per
    run — req_ids come from a process-global counter, so rebuilding the
    trace would break cross-run stream comparison)."""
    import jax

    from repro.configs import get_smoke_config
    from repro.engine.tokenizer import HashTokenizer

    if "m" not in model_cache:
        from repro.models.registry import build_model
        cfg = get_smoke_config("qwen3-1.7b")
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        ds = make_dataset("beer", num_rows=400, seed=4)
        trace = build_trace(ds, TraceConfig(
            num_relqueries=3, rate=100.0, seed=4, max_requests=3,
            num_templates=2, output_token_cap=6),
            tokenizer=HashTokenizer(cfg.vocab_size))
        model_cache["m"] = (model, params, trace)
    return model_cache["m"]


def _real_streams_and_events(backend, loop):
    from repro.serving.factory import build_real_engine

    model, params, trace = _real_fixture()
    trace = copy.deepcopy(trace)
    engine = build_real_engine("qwen3-1.7b-smoke", "relserve", backend,
                               limits=BatchLimits(cap=100_000), max_len=512,
                               model=model, params=params, engine_loop=loop)
    rep = engine.run_trace(trace)
    return _streams(trace), [(e.kind, e.num_requests, e.rel_ids)
                             for e in rep.events]


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_real_backend_pipelined_matches_serial(backend):
    """Dense and paged real JAX executors: split dispatch/wait under the
    pipelined loop yields bit-identical token streams and batch composition
    vs the serial loop (timing differs — wall clock is real here)."""
    st_s, ev_s = _real_streams_and_events(backend, "serial")
    st_p, ev_p = _real_streams_and_events(backend, "pipelined")
    assert st_s == st_p, f"{backend}: pipelined altered a token stream"
    assert ev_s == ev_p, f"{backend}: pipelined altered batch composition"
