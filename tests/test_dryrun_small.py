"""Mini multi-device dry-run in a subprocess (device count is locked at jax
init, so the 512-device production dry-run cannot run inside this process).
Uses an 8-device (2x2x2) mesh and smoke configs — fast, exercises the exact
same cell/lowering/sharding machinery as the production dry-run."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {src!r})
import json
import jax
from repro.configs import get_smoke_config
from repro.launch.cells import build_cell, lower_cell
from repro.launch.hlo_stats import collective_stats, dot_flops
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
out = {{}}
for arch in {archs!r}:
    cfg = get_smoke_config(arch)
    for shape in ("train_4k", "decode_32k"):
        # shrink the assigned shape to smoke scale but keep its kind
        from repro.configs.base import ShapeConfig, SHAPES_BY_NAME
        base = SHAPES_BY_NAME[shape]
        small = ShapeConfig(base.name, base.kind, 64, 8)
        import repro.launch.cells as cells
        import repro.configs as C
        orig = C.SHAPES_BY_NAME[shape]
        C.SHAPES_BY_NAME[shape] = small
        try:
            cell = build_cell(arch, shape, mesh, cfg_override=cfg)
            compiled = lower_cell(cell, mesh).compile()
            txt = compiled.as_text()
            out[f"{{arch}}/{{shape}}"] = {{
                "ok": True,
                "dot_flops": dot_flops(txt),
                "collectives": dict(collective_stats(txt).counts),
            }}
        except Exception as e:
            out[f"{{arch}}/{{shape}}"] = {{"ok": False, "error": f"{{type(e).__name__}}: {{e}}"}}
        finally:
            C.SHAPES_BY_NAME[shape] = orig
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.parametrize("archs", [["qwen3-1.7b", "rwkv6-7b", "qwen3-moe-30b-a3b"]])
def test_small_mesh_dryrun(archs):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT.format(src=os.path.abspath(src), archs=archs)
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][-1]
    results = json.loads(line[len("RESULT:"):])
    for key, r in results.items():
        assert r["ok"], f"{key} failed: {r.get('error')}"
        assert r["dot_flops"] > 0
        # sharded models must communicate
        assert sum(r["collectives"].values()) > 0, f"{key}: no collectives?"
