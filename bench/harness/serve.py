"""Drive the served path on the host's wall clock.

The window runs ``Frontend`` over ``build_real_engine(..., "relserve",
"paged")`` with the system's own defaults (``BatchLimits()``, the pool size
the factory derives from the KV cap, the serial engine loop), and the
traffic's ``max_len`` (``MAX_LEN``, 512, where it states none). The client
here owns a wall clock: it submits each relQuery when it falls due, steps
the engine, and stamps every streamed token on arrival (``on_token``). The
engine's own clock is virtual (it advances by executor durations) and is
read only to report how far it drifts from the wall.

Set-up warms every program shape the window can reach through the
executor's public batch interface, on requests of its own that never reach
the scheduler, so nothing compiles inside the window.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

MAX_LEN = 512
BLOCK = 16


@dataclass
class Row:
    qid: str
    index: int
    prompt: List[int]
    max_output: int
    tokens: List[int] = field(default_factory=list)
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    finished: bool = False          # its last token arrived by the stop
    request: object = None          # the program's Request
    max_batch: int = 0              # rows of the fullest decode batch it was in


@dataclass
class Tick:
    t_end: float
    prefill_lengths: List[int]      # whole prompts prefilled in this tick
    decode_contexts: List[int]      # context of each decoded row
    pool_used: float                # share of the KV pool's blocks in use


@dataclass
class Window:
    t0: float
    t_end: float                    # the close: t0 + seconds
    seconds: float
    schedule: Dict[str, float]      # qid -> due time, for those due in window
    submitted: Dict[str, float]     # qid -> submit time
    rows: Dict[str, Row]                        # req_id -> row
    ticks: List[Tick]
    counters: Dict[str, float]                  # deltas over the window
    prefill_samples: list
    decode_samples: list
    virtual_latency: Dict[str, float]           # qid -> engine clock latency
    t_stop: float                   # end of the drain (the close, if none)


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _blocks(n: int) -> int:
    return -(-n // BLOCK)


def max_len(mix: dict) -> int:
    """A sequence's KV capacity: the traffic's ``max_len``, else 512."""
    return int(mix.get("max_len", MAX_LEN))


def build_engine(cfg_name: str, model, params, max_len: int = MAX_LEN):
    from repro.serving import build_real_engine
    return build_real_engine(cfg_name, "relserve", "paged", model=model,
                             params=params, max_len=max_len)


def warm_up(engine, queries) -> dict:
    """Run every (batch size, length) class the window can reach through the
    executor once: prefill batches of each power-of-two size up to what the
    KV cap and the pool admit, for each power-of-two class of the traffic's
    prompt lengths; decode batches of each power-of-two size for each
    power-of-two class of context width (in blocks) that prompts plus their
    answers span. Returns what was run."""
    from repro.core.batch import Batch
    from repro.core.relquery import Request

    ex = engine.core.executor
    limits = engine.core.scheduler.limits
    cap = ex.max_len
    prompts = [p for q in queries for p in q.prompts]
    outs = [q.max_output_tokens for q in queries for _ in q.prompts]
    min_fp = min(len(p) + o for p, o in zip(prompts, outs))
    rows_cap = min(limits.max_num_seqs, len(prompts), limits.cap // min_fp)
    pool = ex.num_blocks
    seq = [0]

    def fresh(tokens, max_out):
        seq[0] += 1
        # token 0 first: shares no prefix block with any traffic prompt
        return Request(rel_id="warmup", tokens=tuple([0] + list(tokens[1:])),
                       max_output_tokens=max_out, req_id=f"warmup{seq[0]}")

    # prefill: longest prompt of each power-of-two length class
    by_class: Dict[int, List[int]] = {}
    for p in prompts:
        c = _pow2(len(p))
        if len(p) > len(by_class.get(c, [])):
            by_class[c] = p
    runs = {"prefill": [], "decode": []}

    def sizes(limit):
        """Power-of-two buckets up to the one ``limit`` rows fall in."""
        return [1 << j for j in range(_pow2(limit).bit_length())]

    prefill_max = {}
    for c, p in sorted(by_class.items()):
        n_cls = sum(_pow2(len(q)) == c for q in prompts)
        cap_rows = min(rows_cap, n_cls, pool // _blocks(len(p)))
        prefill_max[c] = _pow2(cap_rows)
        for k in sizes(cap_rows):
            n = min(k, cap_rows)
            ex.execute(Batch.prefill([fresh(p, 1) for _ in range(n)]), 0.0)
            runs["prefill"].append((n, len(p)))

    def prefill_live(tokens, count):
        """``count`` live requests of ``tokens``, prefilled in power-of-two
        batches no larger than the largest warmed size of their class."""
        live = []
        step = prefill_max.get(_pow2(len(tokens)), 1)
        while len(live) < count:
            k = min(step, 1 << ((count - len(live)).bit_length() - 1))
            reqs = [fresh(tokens, 10 ** 6) for _ in range(k)]
            ex.execute(Batch.prefill(reqs), 0.0)
            live += reqs
        return live

    # decode: one row whose width sets the class and the rest the shortest
    # prompt, so that every class down to the narrowest is reached; the
    # long row keeps room for one token per probe
    shortest = min(prompts, key=len)
    w_lo = _blocks(len(shortest) + 1)
    w_hi = _blocks(max(len(p) + o - 1 for p, o in zip(prompts, outs)))
    classes = sorted({min(_pow2(w), _blocks(cap))
                      for w in range(w_lo, w_hi + 1)})
    base = max(by_class.values(), key=len)
    for wc in classes:
        n_long = max(1, min(cap, wc * BLOCK) - 12)
        long_tokens = (list(base) * (n_long // len(base) + 1))[:n_long]
        k_max = min(rows_cap, 1 + (pool - wc) // _blocks(len(shortest) + 12))
        live = prefill_live(long_tokens, 1) + prefill_live(shortest, k_max - 1)
        for k in reversed(sizes(k_max)):
            ex.execute(Batch.decode(live[:min(k, k_max)]), 0.0)
            runs["decode"].append((min(k, k_max), wc))
        for r in live:
            ex.release_request(r.req_id)
    if ex.bm.free_blocks != ex.bm.num_blocks:
        raise RuntimeError("warm-up left blocks allocated")
    return runs


def run_window(engine, queries, seconds: float, *, preroll_s: float = 0.0,
               drain_s: float = 0.0, on_open=None, on_close=None,
               annotate=None) -> Window:
    """Serve ``queries`` open loop on the wall clock: from ``preroll_s``
    before the window opens, through the ``seconds`` of the window, and on
    for up to ``drain_s`` after the close until every row of the relQueries
    due in the window has finished. ``on_open`` and ``on_close`` run when
    the window opens and closes. Ticks, step samples and scheduler counters
    are those of the window; compiles are counted to the stop. The window's
    counters also hold the window's change in every numeric entry of the
    executor's ``counters()``, where the program defines it."""
    from repro.core.relquery import RequestState, make_relquery
    from repro.serving import Frontend

    core, ex = engine.core, engine.core.executor
    span = annotate or (lambda name: nullcontext())
    fe = Frontend(engine)
    rows: Dict[str, Row] = {}
    ticks: List[Tick] = []
    now = time.perf_counter
    state = {"open": False}

    def on_token(req_id, tok):
        t = now()
        row = rows[req_id]
        row.tokens.append(tok)
        if row.t_first is None:
            row.t_first = t
        row.t_last = t

    prev = core.on_batch

    def on_batch(event, batch, result):
        with span("bench.deliver"):
            prev(event, batch, result)
        dec = [r for r in batch.decode_requests if r.req_id in result.outputs]
        for r in dec:
            row = rows[r.req_id]
            row.max_batch = max(row.max_batch, len(dec))
        if state["open"]:
            pre = [len(r.prefill_token_ids()) for r in batch.prefill_requests
                   if r.prefilled and r.req_id in result.outputs]
            ticks.append(Tick(now(), pre,
                              [r.num_prompt_tokens + len(r.output_tokens) - 1
                               for r in dec],
                              1.0 - ex.bm.free_blocks / ex.bm.num_blocks))

    core.on_batch = on_batch
    rqs = []
    for q in queries:
        rq = make_relquery(q.qid, q.prompts, 0.0, q.max_output_tokens,
                           template_id=q.qtype, eos_token=0)
        for i, (p, r) in enumerate(zip(q.prompts, rq.requests)):
            rows[r.req_id] = Row(q.qid, i, p, q.max_output_tokens, request=r)
        rqs.append(rq)
    in_window = {q.qid for q in queries if 0.0 <= q.due < seconds}
    measured = [r for r in rows.values() if r.qid in in_window]

    def counters():
        return {"compile_count": ex.compile_count,
                "schedule_s": core.schedule_time + core.schedule_retry_time,
                "ticks": core.iterations, "pre": len(ex.prefill_samples),
                "dec": len(ex.decode_samples), "program": program_counters()}

    def program_counters():
        own = getattr(ex, "counters", None)
        if not callable(own):
            return {}
        return {k: v for k, v in own().items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}

    submitted = {}
    t0 = now() + preroll_s
    t_end = t0 + seconds
    t_cap = t_end + drain_s
    at_open = at_close = None
    i = 0
    while True:
        t = now()
        if at_open is None and t >= t0:
            at_open, state["open"] = counters(), True
            if on_open:
                on_open()
        if at_close is None and t >= t_end:
            at_close, state["open"] = counters(), False
            if on_close:
                on_close()
            t = now()
        if at_close is not None and (t >= t_cap or all(
                r.request.state is RequestState.FINISHED for r in measured)):
            break
        while i < len(queries) and t0 + queries[i].due <= t:
            with span("bench.generate"):
                fe.submit(rqs[i], on_token=on_token)
            submitted[queries[i].qid] = now()
            i += 1
        if fe.has_work():
            fe.step()
        else:
            marks = [m for m in (t0 if at_open is None else None,
                                 t_end if at_close is None else t_cap,
                                 t0 + queries[i].due if i < len(queries)
                                 else None) if m is not None]
            with span("bench.idle"):
                time.sleep(max(0.0, min(marks) - now()))
    t_stop = now()
    core.on_batch = prev
    fe.close()
    at_stop = counters()
    window_counters = {
        "compiles": at_stop["compile_count"] - at_open["compile_count"],
        "schedule_s": at_close["schedule_s"] - at_open["schedule_s"],
        "ticks": at_close["ticks"] - at_open["ticks"],
    }
    for k, v in at_close["program"].items():
        window_counters.setdefault(k, v - at_open["program"].get(k, 0))
    virtual = {rq.rel_id: rq.latency() for rq in rqs
               if rq.latency() is not None}
    for row in rows.values():
        row.finished = (row.request.state is RequestState.FINISHED
                        and row.t_last is not None and row.t_last <= t_stop)
    schedule = {q.qid: t0 + q.due for q in queries if q.qid in in_window}
    return Window(t0, t_end, seconds, schedule,
                  {q: s for q, s in submitted.items() if q in schedule},
                  rows, ticks, window_counters,
                  list(ex.prefill_samples[at_open["pre"]:at_close["pre"]]),
                  list(ex.decode_samples[at_open["dec"]:at_close["dec"]]),
                  virtual, t_stop)
