"""Local expert-parallel MoE dispatch (shard_map) must match the unsharded
dispatch on a real multi-device mesh (§Perf cell B optimization)."""
import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, __SRC__)
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.distributed.sharding import ParallelConfig
from repro.models.moe import moe_dispatch, moe_dispatch_local_ep

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
jax.set_mesh(mesh)
pc = ParallelConfig.from_mesh(mesh)

rng = np.random.RandomState(0)
T, D, F, E, K = 32, 16, 24, 8, 2
x = jnp.asarray(rng.randn(T, D).astype(np.float32))
router = jnp.asarray(rng.randn(D, E).astype(np.float32))
wg = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.2)
wu = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.2)
wd = jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.2)

# capacity == E x avg so nothing drops; local path uses per-shard capacity
out_ref, aux_ref = moe_dispatch(x, router, wg, wu, wd, top_k=K,
                                capacity_factor=float(E), act="silu")
with mesh:
    out_ep, aux_ep = jax.jit(lambda *a: moe_dispatch_local_ep(
        *a, top_k=K, capacity_factor=float(E), act="silu", mesh=mesh, pc=pc))(
        x, router, wg, wu, wd)
err = float(jnp.max(jnp.abs(out_ep - out_ref)))
aerr = abs(float(aux_ep) - float(aux_ref))
print("RESULT:" + json.dumps({"err": err, "aux_err": aerr,
                              "scale": float(jnp.max(jnp.abs(out_ref)))}))
"""


def test_local_ep_dispatch_matches_reference():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT.replace("__SRC__", repr(src))],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][-1]
    r = json.loads(line[len("RESULT:"):])
    assert r["err"] < 1e-4 * max(r["scale"], 1.0), r
    # aux is a local-mean vs global-mean of the same statistic; close but the
    # top-1 fractions are computed per shard — allow small deviation
    assert r["aux_err"] < 0.5, r
