#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own spans and scopes.

    python3 bench/trace_program.py --workload <cell> --seed <n> --seconds <s>

Runs the cell's window as ``run.py --trace 1`` does (the same set-up,
traffic, warm-up, benchmark spans and profiler), with the program's
``relserve.*`` spans turned on, and reads the program's side of the
trace: device seconds by step program and by named scope, which program
and scope run each of the heaviest op kinds, the longest idle gaps named
by the innermost ``relserve.*`` span, and host ms per tick. It also reads the engine's wall-clock phase stamps and its
cost-model samples. No correctness check: the numbers are readings, not a
benchmark result. Prints readings, then one JSON line. ``--dump PATH``
writes the extracted trace, the op scopes and the cost samples there
(gzipped JSON), for reading again without the chip.

Needs the chip the cell runs on, and a program that has the spans
(``repro.engine.tracing``); without either it exits 3.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

import run

say = run.say


def span_cost_ns(tracing, n: int = 20000) -> dict:
    """Host ns per ``relserve.*`` span: tracing off, and on under an active
    profiler trace."""
    import jax

    def per_span():
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with tracing.span("relserve.cost", tick=0):
                pass
        return (time.perf_counter_ns() - t0) / n

    was = tracing.enabled()
    tracing.disable()
    off = per_span()
    tracing.enable()
    log_dir = tempfile.mkdtemp(prefix="span-cost-")
    jax.profiler.start_trace(log_dir)
    on = per_span()
    jax.profiler.stop_trace()
    shutil.rmtree(log_dir, ignore_errors=True)
    if not was:
        tracing.disable()
    return {"off_ns": off, "on_ns": on}


def trace_cell(workload: str, seed: int, seconds: float, *, bench: dict,
               config_dir: Path = run.BENCH / "configs",
               traffic_dir: Path = run.BENCH / "traffic",
               metrics_dir: Path = run.BENCH / "metrics",
               allow_cpu: bool = False, compile_cache: bool = True,
               dump: str = None, t_start: float = None) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    spec = run.cell_spec(bench, workload)
    cell = spec["cell"]
    devs = run.check_devices(cell["chips"], allow_cpu)
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax

    from harness import client, model as hmodel, program_trace, serve, traffic
    from harness import trace as htrace
    from harness.peaks import peaks_for
    from harness.work import KERNEL_OPS
    from repro.engine import tracing

    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    if compile_cache:
        run.use_compile_cache()
    cfg = hmodel.load_config(cell["config"], config_dir)
    mix = traffic.load_mix(traffic_dir / f"{cell['traffic']}.json")
    drain_s = float(mix.get("drain_s", 0.0))
    queries = traffic.generate(mix, seed, seconds + drain_s, cfg["vocab_size"])
    params = jax.block_until_ready(hmodel.make_params(cfg, seed))
    engine = serve.build_engine(cfg["name"], hmodel.build(cfg), params,
                                max_len=serve.max_len(mix))
    core, ex = engine.core, engine.core.executor
    serve.warm_up(engine, queries)

    tracing.enable()
    span = run.span_factory(True)
    log_dir = tempfile.mkdtemp(prefix="program-trace-")
    run.instrument(engine, span)
    hooks = run.trace_hooks(log_dir, span)
    marks = []

    def mark(hook):
        def f():
            marks.append(len(core.cost_samples))
            hook()
        return f
    window = serve.run_window(
        engine, queries, seconds, preroll_s=float(mix.get("preroll_s", 0.0)),
        drain_s=drain_s, annotate=span, on_open=mark(hooks["on_open"]),
        on_close=mark(hooks["on_close"]))
    tracing.disable()
    setup_s = window.t0 - t_start
    mem_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    cost = core.cost_samples[marks[0]:marks[1]]

    pex = program_trace.extract(log_dir)
    scopes = program_trace.hlo_scopes(program_trace.hlo_texts(ex))
    utok = sum(n for n, _ in window.prefill_samples)
    if dump:
        ops = {p: [[program_trace.instruction(o[0]), *o[1:]] for o in v]
               for p, v in pex["ops"].items()}
        compact = {"spans": pex["spans"], "modules": pex["modules"],
                   "ops": ops, "scopes": [[*k, v] for k, v in scopes.items()],
                   "cost": cost, "prefill_utok": utok}
        Path(dump).parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(dump, "wt") as f:
            json.dump(compact, f)
    red = program_trace.reduce(pex, scopes)
    reduced = htrace.reduce(htrace.extract(log_dir), KERNEL_OPS) \
        if pex["ops"] else None
    del engine, core, ex
    gc.collect()
    shutil.rmtree(log_dir, ignore_errors=True)

    ctx = SimpleNamespace(window=window, cfg=cfg, peaks=peaks, trace=reduced,
                          setup_s=setup_s, client=client)
    metrics = {}
    for m in spec["end_to_end"] + spec["per_layer"]:
        value = run.load_reader(m["name"], metrics_dir)(ctx)
        if value is not None:
            metrics[m["name"]] = value
    prefill_s = program_trace.device_s(red, ("prefill_step", "scatter_prefill"))
    readings = {
        "host_ms_per_tick": red["host_ms_per_tick"],
        "decode_device_ms": program_trace.device_ms_per_call(red, "decode_step"),
        "prefill_device_ms_per_ktok": prefill_s * 1e6 / utok
        if utok and prefill_s else None,
        "cost_model_error": program_trace.cost_model_error(cost),
        "ticks": len(cost),
    }
    out = {"workload": workload, "seed": seed,
           "metrics": metrics, "readings": readings,
           "by_program": red["by_program"], "by_scope": red["by_scope"],
           "owners": red["owners"], "idle_gaps": red["idle_gaps"],
           "span_cost": span_cost_ns(tracing),
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devs), "memory_peak_bytes": mem_peak}}
    if reduced is not None:
        out["device"].update(busy_s=reduced["busy_s"],
                             window_s=reduced["window_s"])
        out["device_ops"] = reduced["device_ops"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    try:
        out = trace_cell(args.workload, args.seed, args.seconds, bench=bench,
                         dump=args.dump, t_start=T_PROCESS)
    except run.NoChip as e:
        print(f"trace_program: {e}", file=sys.stderr)
        return 3
    except ImportError as e:
        print(f"trace_program: the program has no spans ({e})",
              file=sys.stderr)
        return 3
    say("device seconds by program", json.dumps(out["by_program"]))
    say("device seconds by scope", json.dumps(out["by_scope"]))
    say("op kinds by program/scope", json.dumps(out["owners"]))
    say("idle gaps by program span", json.dumps(out["idle_gaps"]))
    say("readings", json.dumps(out["readings"]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
