"""Trace reduction: busy and idle time, kernel time, roofline and MFU."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import run
from harness import trace, work
from harness.peaks import peaks_for
from harness.serve import Tick

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6   # ns


def synthetic():
    """A 10 ms window on one device: a fusion that reads a kernel's output
    (1 ms inside), two overlapping paged_attention calls (union 3 ms), a
    while loop (a container) around a flash_prefill call, a gap covered by
    a scheduler span inside a dispatch span, and one op after the window.
    Trace names are the ops' HLO text."""
    return {
        "devices": {"/device:TPU:0": [
            ("%fusion.1 = bf16[8] fusion(%paged_attention.3)", -2 * MS, 3 * MS),
            ("%paged_attention.3 = bf16[8] custom-call(%x)", 2 * MS, 2 * MS),
            ("%paged_attention.4 = bf16[8] custom-call(%x)", 3 * MS, 2 * MS),
            ("%while.3 = (s32[]) while(%t)", 6.5 * MS, 2 * MS),
            ("%flash_prefill.1 = bf16[8] custom-call(%q)", 7 * MS, 1 * MS),
            ("%fusion.2 = bf16[8] fusion(%y)", 12 * MS, 1 * MS)]},  # after
        "host": [("bench.window", 0.0, 10 * MS),
                 ("bench.dispatch", 5 * MS, 2 * MS),
                 ("bench.schedule", 5.5 * MS, 1 * MS),
                 ("bench.wait", 8 * MS, 2 * MS)]}


def test_reduce_synthetic():
    r = trace.reduce(synthetic(), work.KERNEL_OPS)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.006)        # 1 + 3 + 2 ms
    assert r["kernel_s"]["paged_attention"] == pytest.approx(0.004)
    assert r["kernel_s"]["flash_prefill"] == pytest.approx(0.001)
    kinds = dict(r["device_ops"])
    assert kinds == {"paged_attention": pytest.approx(0.004),
                     "flash_prefill": pytest.approx(0.001),
                     "fusion": pytest.approx(0.001)}
    gaps = r["idle_gaps"]
    assert len(gaps) == 3
    assert ["bench.schedule", pytest.approx(0.0015)] in gaps[:2]  # 5-6.5 ms
    assert ["bench.wait", pytest.approx(0.0015)] in gaps[:2]      # 8.5-10 ms
    assert gaps[2] == ["none", pytest.approx(0.001)]              # 1-2 ms


def test_op_seconds_of_every_kind():
    """``op_s`` holds every op kind's seconds, beyond the top ones, and
    ``kernel_s`` reads the same seconds for the two kernels."""
    r = trace.reduce(synthetic(), work.KERNEL_OPS, top=1)
    assert r["device_ops"] == [["paged_attention", pytest.approx(0.004)]]
    assert r["op_s"] == {"paged_attention": pytest.approx(0.004),
                         "flash_prefill": pytest.approx(0.001),
                         "fusion": pytest.approx(0.001)}
    assert r["kernel_s"] == {k: r["op_s"][k] for k in work.KERNEL_OPS}


@pytest.mark.parametrize("name,kind", [
    ("%copy_bitcast_fusion.54 = bf16[28,1281]{1,0} fusion(%p.1)",
     "copy_bitcast_fusion"),
    ("%fusion.809 = bf16[64] fusion(%paged_attention.36)", "fusion"),
    ("%paged_attention.36 = bf16[64,8,2,128] custom-call(%bt.1)",
     "paged_attention"),
    ("copy-done.3", "copy-done"),
])
def test_op_kind_is_the_ops_own_name(name, kind):
    assert trace.op_kind(name) == kind


def test_reduce_needs_the_window_and_device_ops():
    ex = synthetic()
    with pytest.raises(RuntimeError):
        trace.reduce({"devices": ex["devices"], "host": []}, work.KERNEL_OPS)
    with pytest.raises(RuntimeError):
        trace.reduce({"devices": {}, "host": ex["host"]}, work.KERNEL_OPS)


def test_recorded_trace():
    """A window recorded on a TPU v5e (one decode-heavy stretch of the
    qwen3-1.7b cell), reduced by the same code as every run."""
    ex = json.loads((DATA / "trace_small.json").read_text())
    r = trace.reduce(ex, work.KERNEL_OPS)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["kernel_s"]["paged_attention"] > 0
    want = json.loads((DATA / "trace_small_expected.json").read_text())
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["kernel_s"] == pytest.approx(want["kernel_s"])


def test_extract_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ex = trace.extract(tmp_path)
    names = {e[0] for e in ex["host"]}
    assert {"bench.window", "bench.dispatch"} <= names
    # the CPU has no device plane: its ops are the host threads' events
    # that carry an ``hlo_op``, the program's own ops and nothing else
    assert list(ex["devices"]) == ["/host:CPU:0"]
    kinds = {trace.op_kind(e[0]) for e in ex["devices"]["/host:CPU:0"]}
    assert "dot_general" in kinds
    assert not any(k.startswith(("Threadpool", "PjRt", "end:")) for k in kinds)
    r = trace.reduce(ex, work.KERNEL_OPS)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["op_s"]["dot_general"] > 0
    assert r["kernel_s"] == {"paged_attention": 0.0, "flash_prefill": 0.0}


def ctx(ticks, kernel_s, window_s=1.0, busy_s=0.5):
    from harness import model
    cfg = model.load_config("qwen3-1.7b")
    w = SimpleNamespace(ticks=ticks)
    t = {"kernel_s": kernel_s, "window_s": window_s, "busy_s": busy_s}
    return SimpleNamespace(window=w, cfg=cfg, trace=t,
                           peaks=peaks_for("TPU v5 lite"))


def test_paged_attention_roofline_counts_only_the_context():
    ticks = [Tick(0.0, [], [300, 100], 0.5), Tick(0.1, [], [301], 0.5)]
    c = ctx(ticks, {"paged_attention": 0.01})
    read = run.load_reader("paged_attention_roofline.open", run.BENCH / "metrics")
    H, KV, hd, L = 16, 8, 128, 28
    need = 0.0
    for ctxs in ([300, 100], [301]):
        b = sum(2 * KV * hd * n * 2 for n in ctxs) + len(ctxs) * 2 * H * hd * 2
        need += L * b / 819e9            # memory bound
    assert read(c) == pytest.approx(need / 0.01 * 100)
    assert run.load_reader("paged_attention_roofline", run.BENCH / "metrics")(
        ctx(ticks, {"paged_attention": 0.0})) is None


def test_flash_prefill_roofline_and_mfu():
    ticks = [Tick(0.0, [200, 120], [], 0.5)]
    c = ctx(ticks, {"flash_prefill": 0.002})
    f, b = work.prefill_attention(c.cfg, [200, 120])
    assert f == pytest.approx(4 * 16 * 128 * (200 * 201 + 120 * 121) / 2)
    read = run.load_reader("flash_prefill_roofline.backlog", run.BENCH / "metrics")
    assert read(c) == pytest.approx(
        28 * max(f / 197e12, b / 819e9) / 0.002 * 100)
    mfu = run.load_reader("step_mfu.backlog", run.BENCH / "metrics")(c)
    assert mfu == pytest.approx(
        work.model_flops(c.cfg, [200, 120], []) / 197e12 * 100)
    assert 0 < mfu < 100
    idle = run.load_reader("device_idle_share", run.BENCH / "metrics")(c)
    assert idle == pytest.approx(50.0)


def test_model_flops_is_about_two_per_parameter():
    from harness import model
    cfg = model.load_config("qwen3-1.7b")
    per_token = work.model_flops(cfg, [], [1])
    n = model.param_count(cfg)
    assert 2 * n * 0.95 < per_token < 2 * n * 1.2


def test_trace_hooks_record_the_window_span(tmp_path):
    """A profiled window records one ``bench.window`` span around the spans
    made inside it, though the hooks are made before the profiler starts."""
    import jax.numpy as jnp

    span = run.span_factory(True)
    hooks = run.trace_hooks(str(tmp_path), span)
    hooks["on_open"]()
    with span("bench.dispatch"):
        (jnp.arange(8) * 2).block_until_ready()
    hooks["on_close"]()
    host = trace.extract(tmp_path)["host"]
    names = sorted(e[0] for e in host)
    assert names == ["bench.dispatch", "bench.window"]
    (win,) = [e for e in host if e[0] == "bench.window"]
    (dis,) = [e for e in host if e[0] == "bench.dispatch"]
    assert win[1] <= dis[1] and dis[1] + dis[2] <= win[1] + win[2]
