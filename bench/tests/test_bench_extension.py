"""A configuration of another family comes as files of its own: a tiny
sparse-expert decoder on the program's ``MoETransformer``, with its
architecture module, float32 reference, traffic and metric readers all in
``bench/tests/data``, runs a whole traced cell through ``run.run_cell`` on
the CPU with the harness as it stands, and is correct."""
import json
from pathlib import Path

import pytest

import run
from harness import model as hmodel, serve, work

DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny-moe.mix"
SEED = 2 ** 37 + 11


def bench(*readers):
    return {
        "workloads": [{"name": CELL, "config": "tiny-moe",
                       "traffic": "tiny-moe-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "relq_latency_mean_s", "unit": "s"}],
        "per_layer": [{"name": r, "unit": "%", "moves": "relq_latency_mean_s",
                       "workloads": [CELL]} for r in readers],
    }


def run_moe(*readers):
    return run.run_cell(CELL, SEED, 3.0, True, bench=bench(*readers),
                        config_dir=DATA, traffic_dir=DATA, metrics_dir=DATA,
                        allow_cpu=True, compile_cache=False)


def test_moe_counts_only_its_active_experts():
    cfg = hmodel.load_config("tiny-moe", DATA)
    from repro.models.moe import MoETransformer
    assert isinstance(hmodel.build(cfg), MoETransformer)
    L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    F, hd = cfg["moe_intermediate_size"], cfg["head_dim"]
    idle = cfg["num_experts"] - cfg["num_experts_per_tok"]
    norms = 2 * L * D + 2 * L * hd + D
    per_token = hmodel.architecture(cfg).matmul_params_per_token(cfg)
    # every matmul parameter but the embedding, the head and the experts a
    # token does not use
    assert per_token == hmodel.param_count(cfg) - 2 * V * D - norms - \
        L * idle * 3 * D * F
    attention = L * 4 * cfg["num_attention_heads"] * hd     # one context
    assert work.model_flops(cfg, [], [1]) == \
        2 * per_token + attention + 2 * D * V


def test_moe_cell_runs_from_its_own_files(monkeypatch):
    """Correct, the reader reads the decode step's ``mlp`` scope and the
    trace's op seconds, the program's spans stay off (no reader asks for
    them), and the executor's own counters reach the window."""
    from repro.engine import tracing
    from repro.engine.executor import PagedRealExecutor

    enabled = []
    monkeypatch.setattr(tracing, "enable", lambda: enabled.append(1))
    monkeypatch.setattr(
        PagedRealExecutor, "counters", raising=False,
        value=lambda self: {"decode_calls": len(self.decode_samples),
                            "ticks": -1, "kind": "paged"})
    windows = []
    run_window = serve.run_window
    monkeypatch.setattr(serve, "run_window", lambda *a, **k: windows.append(
        run_window(*a, **k)) or windows[-1])

    out = run_moe("expert_share.open")
    assert out["correct"], out["checks"]
    share = out["metrics"]["expert_share.open"]["value"]
    assert 0 < share < 100
    assert not enabled
    (w,) = windows
    assert w.counters["decode_calls"] == len(w.decode_samples) > 0
    assert w.counters["ticks"] > 0 and "kind" not in w.counters
    json.dumps(out)


def test_a_reader_that_asks_for_spans_gets_the_programs_ticks():
    from repro.engine import tracing

    out = run_moe("ticks_traced", "expert_share.open")
    assert out["correct"], out["checks"]
    assert out["metrics"]["ticks_traced"]["value"] > 0
    assert out["metrics"]["expert_share.open"]["value"] > 0
    assert not tracing.enabled()
