"""chip_smoke.py refuses to run without a TPU: it exits non-zero, names the
missing TPU, prints no result line, and fails before importing any of the
serving stack. Run as a child on the CPU only (JAX_PLATFORMS=cpu)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

SCRIPT = Path(__file__).resolve().parent.parent / "chip_smoke.py"

# run chip_smoke as __main__ and report which repro modules it had imported
# when it exited
_WRAPPER = r"""
import runpy, sys
sys.argv = [{script!r}]
try:
    runpy.run_path({script!r}, run_name="__main__")
finally:
    print("REPRO_MODULES=" + ",".join(
        sorted(m for m in sys.modules if m.split(".")[0] == "repro")))
"""


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(args, capture_output=True, text=True, timeout=120,
                          cwd=cwd, env=env)


def test_refuses_without_tpu_before_importing_the_stack():
    proc = _run([sys.executable, "-c", _WRAPPER.format(script=str(SCRIPT))],
                cwd=SCRIPT.parent)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert "REPRO_MODULES=\n" in proc.stdout, proc.stdout


def test_refuses_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run([sys.executable, str(alone)], cwd=tmp_path)
    assert proc.returncode != 0
    assert "no RelServe sources" in proc.stderr
    assert '"ok"' not in proc.stdout
