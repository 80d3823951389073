"""Spans and counters inside the served path.

Tracing off: ``span`` is one shared no-op and a profiled run records no
``relserve.*`` event. Tracing on: every tick is a ``relserve.tick`` span
whose children name the layers a tick crosses, in order, all with the
tick's id. The step programs carry their names and scopes in their lowered
text. Each request's wall-clock stamps are ordered and its relQuery's three
phases sum to finish less admission; one cost sample lands per tick while
tracing is on, and none while it is off.
"""
import functools
import glob
import re

import jax
import pytest

from repro.configs import get_smoke_config
from repro.core.batch import Batch
from repro.core.latency_model import a100_opt13b
from repro.core.policies import SCHEDULERS
from repro.core.priority import BatchLimits
from repro.core.relquery import Request
from repro.data.datasets import make_dataset
from repro.data.trace import TraceConfig, build_trace
from repro.engine import tracing
from repro.engine.engine import ServingEngine
from repro.engine.executor import PagedRealExecutor, RealExecutor, _ExecutorBase
from repro.engine.prefix_cache import PrefixCache
from repro.engine.simulator import SimulatedExecutor
from repro.engine.tokenizer import HashTokenizer
from repro.models.registry import build_model

SCOPES = ("embed", "qkv", "kv_write", "attn", "mlp", "logits")


@functools.lru_cache(maxsize=None)
def _model_and_params():
    cfg = get_smoke_config("qwen3-1.7b")
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.PRNGKey(0))


def _trace(cfg, n_rq=3, n_req=3, out=3, seed=2):
    tok = HashTokenizer(vocab_size=cfg.vocab_size - 2)
    ds = make_dataset("beer", num_rows=64, seed=1)
    trace = build_trace(ds, TraceConfig(num_relqueries=n_rq, rate=5.0,
                                        seed=seed, max_requests=n_req),
                        tokenizer=tok)
    for rq in trace:
        rq.max_output_tokens = out
        for r in rq.requests:
            r.max_output_tokens = out
            r.sim_output_len = out
    return trace


def _paged_engine(engine_loop="serial"):
    cfg, model, params = _model_and_params()
    pc = PrefixCache(block_size=16)
    sched = SCHEDULERS["relserve"](limits=BatchLimits(cap=100_000),
                                   prefix_cache=pc)
    ex = PagedRealExecutor(model, params, num_blocks=128, max_len=256,
                           prefix_cache=pc)
    return cfg, ServingEngine(sched, ex, engine_loop=engine_loop)


def _profiled_spans(tmp_path, run):
    """Run ``run()`` under the profiler; the ``relserve.*`` host events as
    (name, start, duration, args), in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                        for e in line.events
                        if e.name.startswith(tracing.PREFIX)]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture
def traced():
    tracing.enable()
    yield
    tracing.disable()


def test_span_is_one_shared_noop_when_off():
    tracing.disable()
    a = tracing.span("relserve.x", tick=1)
    assert a is tracing.OFF and tracing.span("relserve.y") is a
    assert tracing.tick_span(3) is tracing.OFF
    with a as s:
        s.set_metadata(kind="decode")


def test_tracing_off_records_no_program_span(tmp_path):
    tracing.disable()
    cfg, eng = _paged_engine()
    spans = _profiled_spans(tmp_path, lambda: eng.run_trace(_trace(cfg)))
    assert spans == []


def _children(spans, tick):
    """Top-level program spans inside a tick span (their own children
    dropped), in start order."""
    _, s, d, _ = tick
    inner = [e for e in spans if e[0] != "relserve.tick"
             and s <= e[1] and e[1] + e[2] <= s + d]
    top = [e for e in inner if not any(
        o is not e and o[1] <= e[1] and e[1] + e[2] <= o[1] + o[2]
        and (o[1], -o[2]) < (e[1], -e[2]) for o in inner)]
    return inner, [e[0] for e in top]


@pytest.mark.parametrize("engine_loop", ["serial", "pipelined"])
def test_each_tick_has_its_layers_in_order(tmp_path, traced, engine_loop):
    cfg, eng = _paged_engine(engine_loop)
    spans = _profiled_spans(tmp_path, lambda: eng.run_trace(_trace(cfg)))
    names = {e[0] for e in spans}
    assert {"relserve.admit", "relserve.compile"} <= names
    admits = [e for e in spans if e[0] == "relserve.admit"]
    assert len(admits) == 3 and all("rel_id" in e[3] for e in admits)
    compiles = {e[3]["program"] for e in spans if e[0] == "relserve.compile"}
    assert {"prefill_step", "scatter_prefill", "decode_step"} <= compiles
    ticks = [e for e in spans if e[0] == "relserve.tick"]
    assert len(ticks) == eng.core.iterations > 0
    assert [t[3]["tick"] for t in ticks] == list(range(len(ticks)))
    kinds = set()
    for tick in ticks:
        assert {"kind", "rows", "prefill_tokens"} <= set(tick[3])
        kinds.add(tick[3]["kind"])
        inner, top = _children(spans, tick)
        assert all(e[3].get("tick") == tick[3]["tick"] for e in inner)
        want = ["relserve.dispatch", "relserve.wait", "relserve.complete",
                "relserve.deliver"]
        if engine_loop == "serial" or tick is ticks[0]:
            want = ["relserve.schedule"] + want
        assert [n for n in top if n in want] == want, top
        under = {e[0] for e in inner}
        assert "relserve.host_stash" in under
        if tick[3]["kind"] == "decode":
            assert {"relserve.decode_issue", "relserve.decode_sync"} <= under
        if tick[3]["kind"] == "prefill":
            assert {"relserve.prefill_issue", "relserve.prefill_sync"} <= under
        if "relserve.schedule" in top:
            assert {"relserve.dpu", "relserve.aba"} <= under
    assert {"prefill", "decode"} <= kinds


@pytest.fixture
def lowered(monkeypatch):
    """Lowered text (with debug info) of every program an executor
    compiles, by program name."""
    seen = {}
    aot = _ExecutorBase._aot

    def record(self, fn, *args, **kw):
        seen[self.__class__.__name__, fn.__name__] = fn.lower(
            *args).as_text(debug_info=True)
        return aot(self, fn, *args, **kw)
    monkeypatch.setattr(_ExecutorBase, "_aot", record)
    return seen


def test_step_programs_are_named_and_scoped(lowered):
    cfg, model, params = _model_and_params()
    ex = PagedRealExecutor(model, params, num_blocks=64, max_len=128)
    req = Request("q", tuple(range(1, 20)), 4, req_id="a")
    ex.execute(Batch.prefill([req]), 0.0)
    req.output_tokens.append(1)
    ex.execute(Batch.decode([req]), 0.0)
    ex._copy_block(0, 1)
    dense = RealExecutor(model, params, max_slots=2, max_len=64)
    d = Request("q", tuple(range(1, 20)), 4, req_id="d")
    dense.execute(Batch.prefill([d]), 0.0)
    dense.swap_out("d", 19)
    dense.swap_in("d", 19)
    d.output_tokens.append(1)
    dense.execute(Batch.decode([d]), 0.0)
    paged, dense = "PagedRealExecutor", "RealExecutor"
    for key in ((paged, "prefill_step"), (paged, "scatter_prefill"),
                (paged, "decode_step"), (paged, "copy_block"),
                (dense, "prefill_step"), (dense, "slot_write"),
                (dense, "decode_step"), (dense, "swap_put")):
        assert f"module @jit_{key[1]} " in lowered[key], key
    for key, scopes in (((paged, "decode_step"), SCOPES),
                        ((dense, "decode_step"), SCOPES),
                        ((paged, "prefill_step"), SCOPES),
                        ((paged, "scatter_prefill"), ("kv_write",))):
        text = lowered[key]
        assert f"jit({key[1]})/" in text
        for sc in scopes:
            # a scope inside the prefill's layer scan sits in the scan body's
            # own function, whose locations start at the scope
            assert re.search(f'[/"]{sc}/', text), (key, sc)


def _sim_engine(engine_loop, cap):
    pc = PrefixCache(block_size=16)
    sched = SCHEDULERS["relserve"](limits=BatchLimits(cap=cap),
                                   prefix_cache=pc, kv_admission="optimistic")
    return ServingEngine(sched, SimulatedExecutor(a100_opt13b(),
                                                  prefix_cache=pc),
                         engine_loop=engine_loop)


@pytest.mark.parametrize("engine_loop", ["serial", "pipelined"])
def test_wall_clock_stamps_order_and_phases_sum(engine_loop):
    cfg = get_smoke_config("qwen3-1.7b")
    trace = _trace(cfg, n_rq=6, n_req=8, out=20, seed=5)
    eng = _sim_engine(engine_loop, cap=1200)
    eng.run_trace(trace)
    assert eng.scheduler.preemptions > 0      # the stamps see a restart
    for rq in trace:
        st = [(r.t_admit, r.t_prefill_dispatch, r.t_prefill_done, r.t_finish)
              for r in rq.requests]
        for s in st:
            assert None not in s and s[0] <= s[1] <= s[2] <= s[3]
        admit = min(s[0] for s in st)
        first = min(s[1] for s in st)
        done = max(s[2] for s in st)
        finish = max(s[3] for s in st)
        phases = (first - admit, done - first, finish - done)
        assert all(p >= 0 for p in phases)
        assert sum(phases) == pytest.approx(finish - admit)


@pytest.mark.parametrize("engine_loop", ["serial", "pipelined"])
def test_one_cost_sample_per_tick(engine_loop):
    cfg = get_smoke_config("qwen3-1.7b")
    off = _sim_engine(engine_loop, cap=100_000)
    off.run_trace(_trace(cfg))
    assert off.core.iterations > 0 and off.core.cost_samples == []
    tracing.enable()
    try:
        _, eng = _paged_engine(engine_loop)
        eng.run_trace(_trace(cfg))
        # the price is the scheduler's own model of the batch it chose
        sim = _sim_engine(engine_loop, cap=100_000)
        sim.run_trace(_trace(cfg))
    finally:
        tracing.disable()
    core = eng.core
    assert len(core.cost_samples) == core.iterations > 0
    assert all(p > 0 and m > 0 for p, m in core.cost_samples)
    assert len(sim.core.cost_samples) == sim.core.iterations
    assert [p for p, _ in sim.core.cost_samples] == pytest.approx(
        [m for _, m in sim.core.cost_samples])
