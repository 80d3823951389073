"""The program's side of a trace: device time by step program and scope,
idle gaps named by the program's spans, host time per tick; the wall-clock
phase readers and the cost model's error; and the whole tracing tool at a
small size on the CPU."""
import json
from types import SimpleNamespace

import pytest

import run
import trace_program
from harness import client, program_trace
from harness.serve import Row

MS = 1e6   # ns


def synthetic():
    """A 10 ms window on one device. Two decode_step executions (1-4 ms,
    5-8 ms) and one prefill_step (8.5-9.5 ms); one op after the window and
    a while loop (a container). Two ticks of program spans; the first tick
    blocks 3.4 ms in decode_sync, the second 3.6 ms in prefill_sync. A third
    tick finds no batch: it carries no ``kind`` and is no tick."""
    return {
        "spans": [
            ("bench.window", 0.0, 10 * MS, {}),
            ("relserve.tick", 0.5 * MS, 4.3 * MS, {"tick": 0, "kind": "decode"}),
            ("relserve.schedule", 0.5 * MS, 0.4 * MS, {"tick": 0}),
            ("relserve.dispatch", 0.9 * MS, 0.1 * MS, {"tick": 0}),
            ("relserve.wait", 1.0 * MS, 3.5 * MS, {"tick": 0}),
            ("relserve.decode_sync", 1.0 * MS, 3.4 * MS, {"tick": 0}),
            ("relserve.complete", 4.5 * MS, 0.1 * MS, {"tick": 0}),
            ("relserve.deliver", 4.6 * MS, 0.2 * MS, {"tick": 0}),
            ("relserve.tick", 4.8 * MS, 5.0 * MS, {"tick": 1, "kind": "prefill"}),
            ("relserve.schedule", 4.8 * MS, 0.3 * MS, {"tick": 1}),
            ("relserve.prefill_sync", 6.0 * MS, 3.6 * MS, {"tick": 1}),
            ("bench.wait", 6.0 * MS, 3.7 * MS, {}),
            ("relserve.tick", 9.85 * MS, 0.1 * MS, {"tick": 2}),  # no batch
            ("relserve.schedule", 9.85 * MS, 0.1 * MS, {"tick": 2}),
            ("relserve.tick", 11 * MS, 1 * MS, {"tick": 2, "kind": "decode"}),
        ],
        "modules": {"/device:TPU:0": [("decode_step", 1 * MS, 3 * MS),
                                      ("decode_step", 5 * MS, 3 * MS),
                                      ("prefill_step", 8.5 * MS, 1 * MS)]},
        "ops": {"/device:TPU:0": [
            ("%copy_bitcast_fusion.1 = bf16[8] fusion(%p)", 1 * MS, 2 * MS,
             "decode_step"),
            ("%paged_attention.2 = bf16[8] custom-call(%x)", 3 * MS, 1 * MS,
             "decode_step"),
            ("%copy_bitcast_fusion.1 = bf16[8] fusion(%p)", 5 * MS, 2 * MS,
             "decode_step"),
            ("%fusion.3 = bf16[8] fusion(%y)", 7 * MS, 1 * MS, "decode_step"),
            ("%while.1 = (s32[]) while(%t)", 8.5 * MS, 1 * MS,
             "prefill_step"),
            ("%flash_prefill.1 = bf16[8] custom-call(%q)", 8.5 * MS, 1 * MS,
             "prefill_step"),
            ("%fusion.9 = bf16[8] fusion(%y)", 12 * MS, 1 * MS,
             "decode_step")]},                            # after
    }


# (program, instruction) -> op_name, as the compiled HLO gives them
PRE = "jit(prefill_step)/while/body/closed_call"
SCOPES = {("decode_step", "copy_bitcast_fusion.1"): "jit(decode_step)/attn/squeeze",
          ("decode_step", "paged_attention.2"):
          "jit(decode_step)/attn/paged_attention",
          ("decode_step", "fusion.3"): "jit(decode_step)/mlp/dot_general",
          ("prefill_step", "flash_prefill.1"): f"{PRE}/attn/flash_prefill"}


def test_reduce_synthetic_program_trace():
    r = program_trace.reduce(synthetic(), SCOPES)
    assert r["by_program"] == [["decode_step", pytest.approx(0.006), 2],
                               ["prefill_step", pytest.approx(0.001), 1]]
    assert dict(map(tuple, r["by_scope"])) == {
        "decode_step/attn": pytest.approx(0.005),
        "decode_step/mlp": pytest.approx(0.001),
        "prefill_step/attn": pytest.approx(0.001)}
    assert r["owners"]["copy_bitcast_fusion"] == [
        ["decode_step/attn", pytest.approx(0.004)]]
    assert r["idle_gaps"] == [
        ["relserve.schedule", pytest.approx(0.001)],      # 0-1 ms
        ["relserve.complete", pytest.approx(0.001)],      # 4-5 ms
        ["relserve.prefill_sync", pytest.approx(0.0005)],  # 8-8.5 ms
        ["relserve.tick", pytest.approx(0.0005)]]          # 9.5-10 ms
    assert r["host_ms_per_tick"] == pytest.approx(((4.3 - 3.4) + (5.0 - 3.6)) / 2)
    assert program_trace.device_ms_per_call(r, "decode_step") == pytest.approx(3.0)
    assert program_trace.device_ms_per_call(r, "scatter_prefill") is None
    assert program_trace.device_s(r, ("prefill_step", "scatter_prefill")) == \
        pytest.approx(0.001)


def test_reduce_gives_every_scope_and_the_windows_spans():
    """Every program/scope pair's seconds, not only the top ones, and the
    program's spans that lie inside the window with their arguments."""
    r = program_trace.reduce(synthetic(), SCOPES, top=1)
    assert r["by_scope"] == [["decode_step/attn", pytest.approx(0.005)]]
    assert r["scope_s"] == {("decode_step", "attn"): pytest.approx(0.005),
                            ("decode_step", "mlp"): pytest.approx(0.001),
                            ("prefill_step", "attn"): pytest.approx(0.001)}
    ticks = [sp for sp in r["spans"] if sp[0] == "relserve.tick"]
    assert [t[3] for t in ticks] == [{"tick": 0, "kind": "decode"},
                                     {"tick": 1, "kind": "prefill"},
                                     {"tick": 2}]     # the one at 11 ms is out
    assert all(sp[0].startswith("relserve.") for sp in r["spans"])


def test_scopes_are_the_programs_own(monkeypatch):
    from repro.engine import tracing

    monkeypatch.delattr(tracing, "SCOPES", raising=False)
    assert program_trace._program_scopes() == (
        "embed", "qkv", "kv_write", "attn", "mlp", "logits")
    monkeypatch.setattr(tracing, "SCOPES", ("embed", "attn", "moe"),
                        raising=False)
    assert program_trace._program_scopes() == ("embed", "attn", "moe")


def test_hlo_texts_takes_every_compiled_program():
    """Every compiled executable the executor holds, alone or in a dict of
    shape variants, keyed by the program name the trace gives it."""
    import jax
    import jax.numpy as jnp

    def decode_step(x):
        return x * 2

    def copy_block(x):
        return x + 1

    compiled = lambda f, n: jax.jit(f).lower(jnp.ones((n,))).compile()
    ex = SimpleNamespace(
        _decode_fn={(1, 4): compiled(decode_step, 4),
                    (2, 8): compiled(decode_step, 8)},
        _copy_fn=compiled(copy_block, 4), _other=None, max_len=512,
        decode_samples=[(1, 0.1)])
    texts = program_trace.hlo_texts(ex)
    assert sorted(texts) == ["copy_block", "decode_step"]
    assert len(texts["decode_step"]) == 2
    assert all(t.startswith("HloModule jit_decode_step")
               for t in texts["decode_step"])


def test_without_its_hlo_an_op_has_no_scope():
    r = program_trace.reduce(synthetic())
    assert r["by_scope"] == [["decode_step/-", pytest.approx(0.006)],
                             ["prefill_step/-", pytest.approx(0.001)]]


def test_ops_belong_to_the_program_execution_holding_them():
    ev = [SimpleNamespace(name=n, start_ns=s, duration_ns=d)
          for n, s, d in (("a", 1.0, 1.0), ("b", 5.5, 1.0), ("c", 9.0, 2.0))]
    mods = [("prefill_step", 5.0, 3.0), ("decode_step", 0.0, 4.0)]
    assert [o[3] for o in program_trace._ops(ev, mods)] == \
        ["decode_step", "prefill_step", ""]


def test_a_program_without_spans_gives_empty_readings():
    ex = synthetic()
    ex["spans"] = [s for s in ex["spans"] if not s[0].startswith("relserve.")]
    r = program_trace.reduce(ex)
    assert r["host_ms_per_tick"] is None
    assert {name for name, _ in r["idle_gaps"]} == {"none"}


@pytest.mark.parametrize("module,program", [
    ("jit_decode_step(12)", "decode_step"), ("jit_prefill_step", "prefill_step"),
    ("jit__lambda", "_lambda")])
def test_program_name(module, program):
    assert program_trace.program_name(module) == program


@pytest.mark.parametrize("path,scope", [
    ("jit(decode_step)/attn/paged_attention", "attn"),
    ("jit(prefill_step)/while/body/closed_call/kv_write/pad", "kv_write"),
    ("jit(decode_step)/add", ""), ("", "")])
def test_scope_of(path, scope):
    assert program_trace.scope_of(path) == scope


def _hlo(*instructions):
    body = "".join(f'\n  %{name} = bf16[4]{{0}} fusion(%p.1), kind=kLoop, '
                   f'metadata={{op_name="{path}" source_file="t.py"}}'
                   for name, path in instructions)
    return f"ENTRY %main {{{body}\n  %p.1 = bf16[4]{{0}} parameter(0)\n}}"


def test_hlo_scopes_reads_name_metadata():
    text = _hlo(("copy_bitcast_fusion.7", "jit(decode_step)/attn/squeeze"))
    assert program_trace.hlo_scopes({"decode_step": [text]}) == {
        ("decode_step", "copy_bitcast_fusion.7"): "jit(decode_step)/attn/squeeze"}


def test_op_names_skip_other_metadata_blocks():
    """An instruction's ``op_name`` is found past a block such as
    ``kernel_metadata={}`` on its line; lines without one give nothing."""
    text = ('  %fusion.1 = bf16[4]{0} fusion(%p.1), kind=kLoop, '
            'frontend_attributes={kernel_metadata={}}, '
            'metadata={op_name="jit(decode_step)/attn/pallas_call" id=9}\n'
            '  ROOT %copy.2 = bf16[4]{0} copy(%fusion.1), '
            'metadata={source_file="t.py" op_name="jit(decode_step)/mlp/dot"}\n'
            '  %p.1 = bf16[4]{0} parameter(0)\n'
            '  %c.3 = bf16[4]{0} copy(%p.1), metadata={source_file="t.py"}')
    assert list(program_trace.op_names(text)) == [
        ("fusion.1", "jit(decode_step)/attn/pallas_call"),
        ("copy.2", "jit(decode_step)/mlp/dot")]


def test_hlo_scopes_of_variants_that_disagree_are_ambiguous():
    """Two shape variants of one program: an instruction name both use for
    ops of different scopes is credited to neither."""
    a = _hlo(("fusion.1", "jit(decode_step)/attn/squeeze"),
             ("fusion.2", "jit(decode_step)/mlp/dot_general"),
             ("fusion.3", "jit(decode_step)/kv_write/scatter"))
    b = _hlo(("fusion.1", "jit(decode_step)/attn/reshape"),
             ("fusion.2", "jit(decode_step)/kv_write/scatter"))
    scopes = program_trace.hlo_scopes({"decode_step": [a, b]})
    assert scopes == {
        ("decode_step", "fusion.1"): "jit(decode_step)/attn/squeeze",
        ("decode_step", "fusion.2"): program_trace.AMBIGUOUS,
        ("decode_step", "fusion.3"): "jit(decode_step)/kv_write/scatter"}
    ex = synthetic()
    ex["ops"]["/device:TPU:0"] = [
        ("%fusion.2 = bf16[8] fusion(%y)", 1 * MS, 2 * MS, "decode_step")]
    r = program_trace.reduce(ex, scopes)
    assert r["by_scope"] == [["decode_step/?", pytest.approx(0.002)]]


def test_cost_model_error_is_the_median_relative_error():
    assert program_trace.cost_model_error([(2.0, 1.0), (0.5, 1.0), (1.0, 1.0)]) \
        == pytest.approx(50.0)
    assert program_trace.cost_model_error([]) is None


def _request(*stamps):
    keys = ("t_admit", "t_prefill_dispatch", "t_prefill_done", "t_finish")
    return SimpleNamespace(**dict(zip(keys, stamps)))


def _window(requests_by_query, t_stop=100.0):
    rows = {}
    for qid, reqs in requests_by_query.items():
        for i, req in enumerate(reqs):
            rows[f"{qid}-{i}"] = Row(qid, i, [1], 1, request=req)
    return SimpleNamespace(rows=rows, schedule={q: 0.0 for q in requests_by_query},
                           t_stop=t_stop)


def _read(name, window):
    ctx = SimpleNamespace(window=window, client=client)
    return run.load_reader(name, run.BENCH / "metrics")(ctx)


def test_phase_readers():
    w = _window({"a": [_request(1.0, 2.0, 4.0, 9.0), _request(1.0, 3.0, 5.0, 7.0)],
                 "b": [_request(2.0, 2.5, 3.0, 4.0)],
                 "c": [_request(3.0, 20.0, 21.0, None)]})   # not finished
    # a: wait 1, core 3, tail 4; b: 0.5, 0.5, 1; c left out
    assert _read("wait_phase_s.open", w) == pytest.approx(0.75)
    assert _read("core_phase_s.open", w) == pytest.approx(1.75)
    assert _read("tail_phase_s.open", w) == pytest.approx(2.5)
    assert _read("row_wait_p95_s.open", w) == pytest.approx(
        client.percentile([1.0, 2.0, 0.5, 17.0], 95))


def test_phase_readers_give_nothing_without_stamps():
    """A program that does not stamp its requests gives no phases."""
    bare = SimpleNamespace()
    w = _window({"a": [bare, bare]})
    for name in ("wait_phase_s.open", "core_phase_s.open",
                 "tail_phase_s.open", "row_wait_p95_s.open"):
        assert _read(name, w) is None


def test_tool_needs_the_chip():
    assert trace_program.main(["--workload", "qwen3-1.7b.relq-mix.r80",
                               "--seed", "1", "--seconds", "1"]) == 3


def test_tool_runs_a_tiny_cell_on_the_cpu(tmp_path):
    from test_bench_faults import BENCH, CELL, DATA, SEED

    bench = json.loads(json.dumps(BENCH))
    bench["per_layer"] += [
        {"name": n, "unit": "s", "moves": "relq_latency_mean_s",
         "workloads": [CELL]}
        for n in ("wait_phase_s.open", "core_phase_s.open",
                  "tail_phase_s.open", "row_wait_p95_s.open")]
    dump = tmp_path / "trace.json.gz"
    out = trace_program.trace_cell(CELL, SEED, 2.0, bench=bench,
                                   config_dir=DATA, traffic_dir=DATA,
                                   allow_cpu=True, compile_cache=False,
                                   dump=str(dump))
    m = out["metrics"]
    assert {"wait_phase_s.open", "core_phase_s.open", "tail_phase_s.open",
            "row_wait_p95_s.open", "relq_latency_mean_s"} <= set(m)
    phases = m["wait_phase_s.open"] + m["core_phase_s.open"] + \
        m["tail_phase_s.open"]
    # from admission rather than due time: the generator's lag less
    assert 0 < phases <= m["relq_latency_mean_s"]
    rd = out["readings"]
    assert rd["host_ms_per_tick"] > 0 and rd["ticks"] > 0
    assert rd["cost_model_error"] >= 0
    assert out["span_cost"]["off_ns"] < out["span_cost"]["on_ns"]
    assert dump.stat().st_size > 0
