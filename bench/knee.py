#!/usr/bin/env python3
"""The knee of an open mix: the highest rate of relQueries the served path
sustains on this machine's chip.

    python3 bench/knee.py --config qwen3-1.7b \\
        --traffic relq-mix.knee80-qwen3-1.7b --seed <n> --cycles 2

Serves ``--cycles`` whole cycles of the mix's deck (the relQueries of one
cycle, in the order the mix sends them), all due at once, to completion on
the same served path and set-up as ``run.py``; the knee is the relQueries
served over the seconds from the first submit to the last token. An open
cell runs at a fixed share of it (``--share``, 0.8 by default), which this
prints as the ``rate_rps`` to write into the cell's mix file. Needs the chip
the cell runs on; prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--share", type=float, default=0.8)
    args = ap.parse_args(argv)
    try:
        run.check_devices(1, allow_cpu=False)
    except run.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax

    from harness import model as hmodel, serve, traffic

    run.use_compile_cache()
    cfg = hmodel.load_config(args.config)
    mix = traffic.load_mix(run.BENCH / "traffic" / f"{args.traffic}.json")
    n = traffic.deck_size(mix) * args.cycles
    horizon = args.cycles * traffic.deck_size(mix) / mix["rate_rps"]
    queries = [q for q in traffic.generate(mix, args.seed, horizon,
                                           cfg["vocab_size"]) if q.due >= 0][:n]
    for q in queries:
        q.due = 0.0
    params = jax.block_until_ready(hmodel.make_params(cfg, args.seed))
    engine = serve.build_engine(cfg["name"], hmodel.build(cfg), params,
                                max_len=serve.max_len(mix))
    serve.warm_up(engine, queries)
    ex = engine.core.executor
    n_dec = len(ex.decode_samples)
    w = serve.run_window(engine, queries, 1e-3, drain_s=3600.0)
    groups = {}
    for r in w.rows.values():
        groups.setdefault(r.qid, []).append(r)
    done = sorted(max(r.t_last for r in rs) - w.t0 for rs in groups.values()
                  if all(r.finished for r in rs))
    seconds = w.t_stop - w.t0
    knee = len(done) / seconds
    print(json.dumps({
        "config": args.config, "traffic": args.traffic, "seed": args.seed,
        "relqueries": len(queries), "finished": len(done),
        "rows": sum(len(q.prompts) for q in queries), "seconds": seconds,
        "knee_rps": knee, "rate_rps": args.share * knee,
        "finish_s": done, "compiles": w.counters["compiles"],
        "decode_step_median_s": statistics.median(
            d for _, d in ex.decode_samples[n_dec:])}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
