#!/usr/bin/env python3
"""RelServe benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout on the machine that holds the chips. Set-up
makes the weights on the device from the seed, builds the served path and
warms every program shape the cell's traffic can reach; then the window
serves the cell's traffic open loop for ``--seconds`` on the host clock.
After the window the program's state is freed and a sample of what it served
is compared with the configuration's plain float32 reference. ``--trace 1``
runs the same window under the profiler and reports the cell's per-layer
metrics instead of its end-to-end ones. Their readers get the trace's device
time (``ctx.trace``) and the program's side of it (``ctx.program``: device
time by step program and scope, the program's spans, host time per tick);
the program's own spans are on only where a reader asks for them.

Earlier lines of standard output carry readings for people; the last line
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``: each
number compared with its limit. The same numbers end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. ``--control 1`` puts the float8 control of the
correctness comparison in the program's place, so that the run reports
``correct`` false (used to set the limits; the benchmark's runs do not run
it).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import statistics
import sys
import tempfile
from contextlib import nullcontext
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def say(*parts) -> None:
    print("[bench]", *parts, flush=True)


def cell_spec(bench: dict, workload: str) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]

    def here(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", ()) or
             ("workloads" not in m and m["moves"] in names)]
    return {"cell": cell, "end_to_end": e2e, "per_layer": layer}


def reader_module(name: str, directory: Path):
    """``bench/metrics/<name>.py``, else the file of its base name (the part
    before the first dot): its ``read(ctx)`` gives the value or None, and
    ``SPANS = True`` asks for the program's spans in a traced run."""
    for stem in (name, name.split(".")[0]):
        path = directory / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for metric {name!r} in {directory}")


def load_reader(name: str, directory: Path):
    return reader_module(name, directory).read


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names another."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a cell's programs take more than a small size cap holds, and one that
    # evicts the least recently used entry then misses on every program of
    # the next run (they are asked for in the same order)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def check_devices(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    if not allow_cpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def span_factory(on: bool):
    import jax

    if not on:
        return lambda name: nullcontext()
    return lambda name: jax.profiler.TraceAnnotation(name)


def instrument(engine, span) -> None:
    """Benchmark spans around the scheduler and the executor's dispatch and
    wait (instance attributes over the program's methods; traced runs only)."""
    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with span(name):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)

    wrap(engine.core.scheduler, "schedule", "bench.schedule")
    wrap(engine.core.executor, "dispatch", "bench.dispatch")
    wrap(engine.core.executor, "wait", "bench.wait")


def program_spans(on: bool):
    """Turn the program's ``relserve.*`` spans on, where ``on`` and the
    program has them; returns the function that turns them off again."""
    if on:
        try:
            from repro.engine import tracing
        except ImportError:
            return lambda: None
        tracing.enable()
        return tracing.disable
    return lambda: None


def trace_hooks(log_dir: str, span) -> dict:
    """``on_open`` starts the profiler and then the ``bench.window`` span;
    ``on_close`` ends both. The span is made after the profiler starts: an
    annotation made while no trace is active records nothing."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    open_span = []

    def on_open():
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        open_span.append(span("bench.window"))
        open_span[0].__enter__()

    def on_close():
        open_span.pop().__exit__(None, None, None)
        jax.profiler.stop_trace()
    return {"on_open": on_open, "on_close": on_close}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict, config_dir: Path = BENCH / "configs",
             traffic_dir: Path = BENCH / "traffic",
             metrics_dir: Path = BENCH / "metrics", control: bool = False,
             allow_cpu: bool = False, compile_cache: bool = True,
             t_start: float = None) -> dict:
    """One run of one cell; returns the result object (the last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = cell_spec(bench, workload)
    cell = spec["cell"]
    devs = check_devices(cell["chips"], allow_cpu)
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(f"no RelServe sources under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from harness import check, client, model as hmodel, program_trace, serve
    from harness import trace as htrace, traffic
    from harness.peaks import peaks_for
    from harness.work import KERNEL_OPS

    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    say("device", dev.device_kind, "x", len(devs), "| compile cache",
        use_compile_cache() if compile_cache else "off")
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    readers = {m["name"]: reader_module(m["name"], metrics_dir)
               for m in wanted}
    cfg = hmodel.load_config(cell["config"], config_dir)
    mix = traffic.load_mix(traffic_dir / f"{cell['traffic']}.json")
    preroll_s = float(mix.get("preroll_s", 0.0))
    drain_s = float(mix.get("drain_s", 0.0))
    queries = traffic.generate(mix, seed, seconds + drain_s, cfg["vocab_size"])
    say("traffic", json.dumps(traffic.summary(queries)))

    t0 = time.perf_counter()
    params = jax.block_until_ready(hmodel.make_params(cfg, seed))
    say(f"weights {hmodel.param_count(cfg) / 1e9:.3f} B {cfg['torch_dtype']} "
        f"in {time.perf_counter() - t0:.2f} s")
    model = hmodel.build(cfg)
    hmodel.check_layout(params, model)
    engine = serve.build_engine(cfg["name"], model, params,
                                max_len=serve.max_len(mix))
    ex = engine.core.executor
    t0 = time.perf_counter()
    runs = serve.warm_up(engine, queries)
    say(f"warm-up: {len(runs['prefill'])} prefill and {len(runs['decode'])} "
        f"decode batches, {ex.compile_count} programs in "
        f"{ex.compile_s_total:.1f} s compile, {time.perf_counter() - t0:.1f} s")

    span = span_factory(trace)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    hooks = {}
    if trace:
        instrument(engine, span)
        hooks = trace_hooks(log_dir, span)
    spans_off = program_spans(trace and any(
        getattr(r, "SPANS", False) for r in readers.values()))
    try:
        window = serve.run_window(engine, queries, seconds,
                                  preroll_s=preroll_s, drain_s=drain_s,
                                  annotate=span, **hooks)
    finally:
        spans_off()
    # set-up ends when the window opens: the pre-roll is part of it
    setup_s = window.t0 - t_start
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    t_read = time.perf_counter()
    texts = program_trace.hlo_texts(ex) if trace else None
    del engine, ex
    gc.collect()

    reduced = program = None
    if trace:
        import shutil
        pd = htrace.load(log_dir)
        reduced = htrace.reduce(htrace.from_profile(pd), KERNEL_OPS)
        program = program_trace.reduce(program_trace.from_profile(pd),
                                       program_trace.hlo_scopes(texts))
        del pd, texts
        shutil.rmtree(log_dir, ignore_errors=True)
        say(f"trace read in {time.perf_counter() - t_read:.1f} s")
    report_window(window, mem_peak)

    t_ref = time.perf_counter()
    rows = check.sample_rows(window, seed)
    readings = check.compare(cfg, params, rows, control=control) if rows else \
        {"served_tokens": 0.0, "stream_faults": 0.0, "widest_gap": float("inf")}
    say("correctness readings", json.dumps(readings), "over", len(rows),
        "rows, reference", f"{time.perf_counter() - t_ref:.1f} s")
    limits = dict(cfg["correctness"]["limits"])
    checks = check.verdict(readings, limits)
    correct = bool(rows) and all(c["value"] <= c["limit"]
                                 for c in checks.values())

    # what a metric reader may read
    ctx = SimpleNamespace(window=window, cfg=cfg, peaks=peaks, trace=reduced,
                          program=program, setup_s=setup_s, client=client)
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": len(window.schedule),
           "failed": 0, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        say("kernel seconds", json.dumps(reduced["kernel_s"]))
        say("op seconds", json.dumps(reduced["op_s"]))
        say("device seconds by program", json.dumps(program["by_program"]))
        say("device seconds by program/scope",
            json.dumps(sorted(([f"{p}/{sc}", v] for (p, sc), v in
                               program["scope_s"].items()),
                              key=lambda kv: -kv[1])))
        say("host ms per tick", program["host_ms_per_tick"])
    out["checks"] = checks
    return out


def report_window(w, mem_peak: int) -> None:
    from harness import client

    rows = list(w.rows.values())
    due_rows = [r for r in rows if r.qid in w.schedule]
    by_close = sum(r.finished and r.t_last <= w.t_end for r in due_rows)
    done = sum(r.finished for r in due_rows)
    say(f"window {w.seconds:g} s: relQueries due {len(w.schedule)}, "
        f"submitted {len(w.submitted)}; rows due {len(due_rows)}, finished "
        f"by the close {by_close}, by the stop {done} (drain "
        f"{w.t_stop - w.t_end:.2f} s), censored {len(due_rows) - done}; "
        f"rows finished in the window (all) {client.rows_finished(w)}; "
        f"ticks {w.counters['ticks']}")
    say(f"compiles from the open to the stop: {w.counters['compiles']} "
        "(target 0)")
    lags = client.generator_lags(w)
    if lags:
        say(f"generator lag: median {statistics.median(lags) * 1e3:.3f} ms, "
            f"max {max(lags) * 1e3:.3f} ms")
    if w.decode_samples:
        say("decode step median s", statistics.median(
            d for _, d in w.decode_samples), "of", len(w.decode_samples))
    if w.prefill_samples:
        say("prefill batch median s", statistics.median(
            d for _, d in w.prefill_samples), "of", len(w.prefill_samples))
    if w.ticks:
        say(f"KV pool in use: mean {statistics.fmean(t.pool_used for t in w.ticks):.4f}, "
            f"max {max(t.pool_used for t in w.ticks):.4f}")
    lat = client.relq_latencies(w)
    say("relQuery latencies s", json.dumps({q: round(x, 4) for q, x in
                                            zip(w.schedule, lat)}))
    wall = {}
    for qid, due in w.schedule.items():
        rs = [r for r in rows if r.qid == qid]
        if all(r.finished for r in rs):
            wall[qid] = max(r.t_last for r in rs) - due
    both = [q for q in wall if q in w.virtual_latency]
    if both:
        say("engine virtual clock vs wall: relQuery latency mean "
            f"{statistics.mean(w.virtual_latency[q] for q in both):.4f} s "
            f"virtual, {statistics.mean(wall[q] for q in both):.4f} s wall, "
            f"over {len(both)} finished relQueries")
    say(f"peak HBM {mem_peak / 1e9:.3f} GB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), bench=bench,
                       control=bool(args.control), t_start=T_PROCESS)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
