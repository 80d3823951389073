"""Fast-lane drift guard for ``.github/workflows/ci.yml``.

The CI fast job runs an explicit file list (plus the multi-device sharding
trio as its own step), and the full job sweeps everything. That split only
stays honest if every new test module is consciously placed: either added to
the fast lane or recorded in the explicit full-job-only allowlist below.
A module in neither is silent drift — it would run nowhere until the full
job happens to pick it up, with no record of why it skipped the fast lane.

Parsed with regexes, not a yaml library — the workflow is hand-maintained
and the dependency footprint stays zero.
"""
from __future__ import annotations

import re
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
WORKFLOW = REPO / ".github" / "workflows" / "ci.yml"

# The multi-device sharding trio runs as its own fast-job step (subprocess,
# XLA_FLAGS host devices) — still the fast lane, just not the main list.
SHARDING_TRIO = {
    "test_dryrun_small.py",
    "test_moe_dispatch.py",
    "test_seq_parallel.py",
}

# Modules that deliberately run ONLY in the full job: they compile real
# models / kernels and would blow the fast lane's budget. Adding a test
# module to neither the fast list nor this allowlist fails this guard —
# the placement decision must be explicit.
FULL_JOB_ONLY = {
    "test_decode_consistency.py",   # real-model greedy decode parity
    "test_engine_real.py",          # real executor end-to-end
    "test_eos_early_stop.py",       # real-model EOS handling
    "test_gqa_packing.py",          # attention head-packing kernels
    "test_kernels.py",              # pallas kernel suite
    "test_models_smoke.py",         # every registry arch compiles + runs
    "test_roofline_accounting.py",  # flop/byte accounting on real models
    "test_training.py",             # training-loop smoke
    "test_tpu_compile.py",          # kernels compiled for a described v5e
}


def _workflow_text() -> str:
    assert WORKFLOW.exists(), f"workflow file moved? {WORKFLOW}"
    return WORKFLOW.read_text(encoding="utf-8")


def _fast_lane_modules(text: str) -> set:
    """Every tests/test_*.py named anywhere in the workflow. Only the fast
    job lists individual test files (smoke runs CLIs/benches, full sweeps
    the whole suite), so this is exactly the fast lane + sharding trio."""
    return {m.rsplit("/", 1)[1]
            for m in re.findall(r"tests/test_\w+\.py", text)}


def test_every_test_module_has_an_explicit_lane():
    on_disk = {p.name for p in (REPO / "tests").glob("test_*.py")}
    listed = _fast_lane_modules(_workflow_text())
    placed = listed | FULL_JOB_ONLY
    drifted = sorted(on_disk - placed)
    assert not drifted, (
        f"test modules in no CI lane: {drifted} — add them to the fast-job "
        f"list in {WORKFLOW} or to FULL_JOB_ONLY in {__file__} (with a "
        f"reason)")


def test_sharding_trio_step_is_intact():
    listed = _fast_lane_modules(_workflow_text())
    missing = sorted(SHARDING_TRIO - listed)
    assert not missing, (
        f"sharding-trio modules vanished from the workflow: {missing}")


def test_full_only_allowlist_is_not_stale():
    on_disk = {p.name for p in (REPO / "tests").glob("test_*.py")}
    gone = sorted(FULL_JOB_ONLY - on_disk)
    assert not gone, f"FULL_JOB_ONLY names deleted modules: {gone}"
    listed = _fast_lane_modules(_workflow_text())
    both = sorted(FULL_JOB_ONLY & listed)
    assert not both, (
        f"modules both in the fast lane and FULL_JOB_ONLY: {both} — drop "
        f"them from the allowlist")


def test_this_guard_runs_in_the_fast_lane():
    # the guard is useless if it only runs in the full sweep
    assert "test_ci_workflow.py" in _fast_lane_modules(_workflow_text())


def test_nightly_lane_covers_slow_marker_and_bench_smokes():
    text = _workflow_text()
    nightly = text[text.index("nightly:"):]
    assert re.search(r"-m slow", nightly), \
        "nightly job must run the -m slow lanes"
    for bench in ("kv_pressure", "prefix_sharing", "real_executor",
                  "async_engine", "planner", "fault_recovery"):
        assert f"benchmarks.{bench} --smoke" in nightly, \
            f"nightly job lost the {bench} --smoke entry point"
    assert "check_regression" in nightly, \
        "nightly job must gate fresh artifacts against the baselines"
