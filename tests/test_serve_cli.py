"""launch/serve.py CLI: argument validation fails fast with clear messages,
and the --open-loop smoke mode exercises submit/stream/cancel/snapshot."""
import glob
import sys

import pytest

from repro.launch import serve


def _run(monkeypatch, *argv):
    monkeypatch.setattr(sys, "argv", ["serve", *argv])
    serve.main()


@pytest.mark.parametrize("argv,match", [
    (["--simulate", "--rate", "0"], "--rate must be > 0"),
    (["--simulate", "--rate", "-1.5"], "--rate must be > 0"),
    (["--simulate", "--num-relqueries", "0"], "--num-relqueries must be >= 1"),
    (["--simulate", "--max-requests", "0"], "--max-requests must be >= 1"),
    (["--simulate", "--num-replicas", "0"], "--num-replicas must be >= 1"),
    (["--simulate", "--kv-tiering", "on"],
     "--kv-tiering on requires a preempting admission"),
    (["--simulate", "--host-kv-cap", "4096"],
     "--host-kv-cap only applies with --kv-tiering on"),
    (["--simulate", "--swap-bandwidth", "16"],
     "--swap-bandwidth only applies with --kv-tiering on"),
    (["--simulate", "--kv-tiering", "on", "--kv-admission", "optimistic",
      "--host-kv-cap", "0"], "--host-kv-cap must be >= 1"),
    (["--simulate", "--kv-tiering", "on", "--kv-admission", "optimistic",
      "--swap-bandwidth", "0"], "--swap-bandwidth must be > 0 GB/s"),
    (["--simulate", "--profile-dir", "trace"],
     "--profile-dir traces the real executors"),
])
def test_cli_validation(monkeypatch, argv, match):
    with pytest.raises(SystemExit, match=match):
        _run(monkeypatch, *argv)


def test_simulated_tiering_smoke(monkeypatch, capsys):
    """A tight --kv-cap plus --kv-tiering on actually swaps, reports the
    swap counters, and still completes the whole trace."""
    _run(monkeypatch, "--simulate", "--num-relqueries", "10", "--rate", "3.0",
         "--max-requests", "10", "--kv-admission", "optimistic",
         "--kv-cap", "400", "--kv-tiering", "on", "--debug-invariants")
    out = capsys.readouterr().out
    assert "kv-tiering=on" in out
    assert "[merged] relqueries=10" in out
    assert "kv-tiering:" in out and "swap-outs" in out


def test_predicted_admission_smoke(monkeypatch, capsys):
    _run(monkeypatch, "--simulate", "--num-relqueries", "8",
         "--max-requests", "8", "--rate", "4.0",
         "--kv-admission", "predicted")
    out = capsys.readouterr().out
    assert "[merged] relqueries=8" in out


def test_open_loop_smoke_simulated(monkeypatch, capsys):
    _run(monkeypatch, "--simulate", "--open-loop", "--num-relqueries", "12",
         "--rate", "3.0", "--max-requests", "10", "--num-replicas", "2")
    out = capsys.readouterr().out
    assert "OPEN-LOOP SMOKE OK" in out
    assert "cancelled" in out and "tokens streamed" in out


def test_closed_loop_simulated_still_works(monkeypatch, capsys):
    _run(monkeypatch, "--simulate", "--num-relqueries", "8",
         "--max-requests", "8", "--rate", "4.0")
    out = capsys.readouterr().out
    assert "[merged] relqueries=8" in out


def test_profile_dir_records_the_program_spans(monkeypatch, capsys, tmp_path):
    """Real mode under --profile-dir writes one profiler trace holding the
    engine's relserve.* spans, and leaves tracing off afterwards."""
    import jax

    from repro.engine import tracing

    _run(monkeypatch, "--arch", "qwen3-1.7b-smoke", "--kv-backend", "paged",
         "--num-relqueries", "2", "--max-requests", "2",
         "--profile-dir", str(tmp_path))
    assert "profile: trace with relserve.* spans" in capsys.readouterr().out
    assert not tracing.enabled()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"relserve.admit", "relserve.tick", "relserve.schedule",
            "relserve.dispatch", "relserve.wait", "relserve.decode_sync",
            "relserve.deliver"} <= names
