"""Sequence-parallel decode (beyond-paper optimization) correctness: on a real
multi-device mesh (subprocess, 8 host devices), the SP decode step must
reproduce the baseline packed-TP decode step given equivalent weights and a
resharded cache."""
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
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.distributed.sharding import ParallelConfig, pack_q_weight, pack_kv_weight
from repro.models.transformer import DenseTransformer
from repro.models.seq_parallel import SeqParallelDenseTransformer, reshard_cache_from_packed

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
jax.set_mesh(mesh)
pc = ParallelConfig.from_mesh(mesh)
cfg = get_smoke_config("qwen3-1.7b").replace(num_layers=2)
base = DenseTransformer(cfg, pc)
sp = SeqParallelDenseTransformer(cfg, pc, mesh=mesh)

rng = np.random.RandomState(0)
D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
G = cfg.num_layers

# canonical attention weights -> both layouts
def mk(*shape, scale=0.1):
    return rng.randn(*shape).astype(np.float32) * scale

params_sp = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sp.abstract_params())
params_b = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), base.abstract_params())

shared = {}
for name in ("ln1", "ln2", "q_norm", "k_norm", "w_gate", "w_up", "w_down"):
    shared[name] = mk(*params_sp["blocks"][name].shape)
emb = mk(*params_sp["embed"].shape)
fin = mk(*params_sp["final_norm"].shape)

wq_c = mk(G, 1, D, H, hd)
wk_c = mk(G, 1, D, KV, hd)
wv_c = mk(G, 1, D, KV, hd)
wo_c = mk(G, 1, H, hd, D)

pb = dict(params_b["blocks"])
lay = base.layout
pb["wq"] = jnp.asarray(np.stack([np.stack([
    pack_q_weight(wq_c[g, 0], lay, head_axis=1).reshape(D, lay.kv_slots, lay.q_per_slot, hd)
    for _ in range(1)]) for g in range(G)]), jnp.bfloat16)
pb["wk"] = jnp.asarray(np.stack([np.stack([
    pack_kv_weight(wk_c[g, 0], lay, head_axis=1) for _ in range(1)]) for g in range(G)]), jnp.bfloat16)
pb["wv"] = jnp.asarray(np.stack([np.stack([
    pack_kv_weight(wv_c[g, 0], lay, head_axis=1) for _ in range(1)]) for g in range(G)]), jnp.bfloat16)
pb["wo"] = jnp.asarray(np.stack([np.stack([
    pack_q_weight(wo_c[g, 0], lay, head_axis=0).reshape(lay.kv_slots, lay.q_per_slot, hd, D)
    for _ in range(1)]) for g in range(G)]), jnp.bfloat16)
for name, v in shared.items():
    pb[name] = jnp.asarray(v, jnp.bfloat16)
params_b = {"embed": jnp.asarray(emb, jnp.bfloat16), "blocks": pb, "final_norm": jnp.asarray(fin, jnp.bfloat16)}

ps = dict(params_sp["blocks"])
ps["wq"] = jnp.asarray(wq_c, jnp.bfloat16)
ps["wk"] = jnp.asarray(wk_c, jnp.bfloat16)
ps["wv"] = jnp.asarray(wv_c, jnp.bfloat16)
ps["wo"] = jnp.asarray(wo_c.reshape(G, 1, H * hd, D), jnp.bfloat16)
for name, v in shared.items():
    ps[name] = jnp.asarray(v, jnp.bfloat16)
params_sp = {"embed": jnp.asarray(emb, jnp.bfloat16), "blocks": ps, "final_norm": jnp.asarray(fin, jnp.bfloat16)}

# prefill on baseline -> decode on both
B, S, MAX = 2, 12, 16
toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32)
_, cache_b = base.prefill(params_b, toks, max_len=MAX)
cache_sp = reshard_cache_from_packed(cache_b, base, sp)

new_tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (B,)), jnp.int32)
pos = jnp.full((B,), S, jnp.int32)
lg_b, _ = base.decode_step(params_b, cache_b, new_tok, pos)

with mesh:
    step = jax.jit(sp.decode_step)
    lg_sp, cache_sp2 = step(params_sp, cache_sp, new_tok, pos)

err = float(jnp.max(jnp.abs(lg_sp.astype(jnp.float32) - lg_b.astype(jnp.float32))))
scale = float(jnp.max(jnp.abs(lg_b)))
# second decode step: cache write must have landed in the right chunk
lg_sp2, _ = step(params_sp, cache_sp2, new_tok, pos + 1)
_, cb2 = base.decode_step(params_b, cache_b, new_tok, pos)
lg_b2, _ = base.decode_step(params_b, cb2, new_tok, pos + 1)
err2 = float(jnp.max(jnp.abs(lg_sp2.astype(jnp.float32) - lg_b2.astype(jnp.float32))))
print("RESULT:" + json.dumps({"err": err, "err2": err2, "scale": scale}))
"""


def test_seq_parallel_decode_matches_baseline():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT.replace("__SRC__", repr(src))],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][-1]
    r = json.loads(line[len("RESULT:"):])
    tol = 0.02 * max(r["scale"], 1.0)
    assert r["err"] < tol, f"first decode mismatch: {r}"
    assert r["err2"] < tol, f"second decode mismatch (cache write broken): {r}"
