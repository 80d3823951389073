"""A configuration file, the program's model built from it, and its weights.

A configuration (``bench/configs/<name>.json``) holds the published
``config.json`` keys of the model as it is run here, and names two modules
of its own by their paths under ``bench/``: ``architecture`` (the program's
``ModelConfig``, the parameter layout, the matmul parameters a token passes
through and the layers that run attention; ``arch/qwen.py`` is one) and
``reference`` (the plain float32 forward pass, ``logits_at``). Weights are
made on the device from the seed, in one jitted call, in the dtype they are
served in, laid out as the program's single-device parameter tree. The
plain reference reads the same arrays and reshapes them itself; nothing the
program makes reaches it.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]

# scales of the parameters that published configs leave at 0 or 1 (norm
# offsets, biases): drawn away from that value so that a path that drops
# them shows in the logits
NORM_STD = 0.1
BIAS_STD = 0.5


def load_config(name: str, directory: Path = BENCH / "configs") -> dict:
    path = Path(directory) / f"{name}.json"
    cfg = json.loads(path.read_text())
    for key in ("architecture", "reference"):
        if key not in cfg:
            raise KeyError(f"{path} names no {key!r} module (a path under "
                           f"{BENCH})")
    if "num_attention_heads" in cfg:
        cfg.setdefault("head_dim",
                       cfg["hidden_size"] // cfg["num_attention_heads"])
    return cfg


def load_module(rel: str):
    """The module at ``bench/<rel>``, loaded once per process."""
    path = (BENCH / rel).resolve()
    name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(BENCH.resolve())))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def architecture(cfg: dict):
    """The configuration's architecture module: ``program_config``,
    ``param_layout``, ``matmul_params_per_token``, ``attention_layers``."""
    return load_module(cfg["architecture"])


def reference(cfg: dict):
    """The configuration's plain reference module (``logits_at``)."""
    return load_module(cfg["reference"])


def build(cfg: dict):
    """The program's model for a configuration."""
    from repro.models.registry import build_model
    return build_model(architecture(cfg).program_config(cfg))


def _uniform(key, shape, std, dtype):
    a = std * math.sqrt(3.0)        # uniform(-a, a) has this std
    return jax.random.uniform(key, shape, dtype, -a, a)


def make_params(cfg: dict, seed: int):
    """All weights from ``seed`` in one jitted call on the default device,
    uniform draws of the architecture's ``param_layout``. A top-level entry
    that is a dict is a group of stacked layer leaves, drawn layer by layer
    (``lax.map``), so no temporary larger than one layer's leaf is alive at
    once. Keys go to the groups' leaves (groups and leaves in name order),
    then to the top-level leaves in name order."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    lay = architecture(cfg).param_layout(cfg)
    groups = sorted(g for g in lay if isinstance(lay[g], dict))
    leaves = sorted(n for n in lay if n not in groups)

    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        names = [(g, n) for g in groups for n in sorted(lay[g])] + \
            [(None, n) for n in leaves]
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {n: _uniform(keys[None, n], *lay[n], dtype) for n in leaves}
        for g in groups:
            out[g] = {}
            for n, (shape, std) in lay[g].items():
                layer_keys = jax.random.split(keys[g, n], shape[0])
                out[g][n] = jax.lax.map(
                    lambda k, s=shape[1:], sd=std: _uniform(k, s, sd, dtype),
                    layer_keys)
        return out

    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.jit(draw)(jnp.uint32(lo), jnp.uint32(hi))


def check_layout(params, model) -> None:
    """Fail loudly if the program's parameter tree no longer matches the
    layout the weights and the reference assume."""
    want = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)),
                        model.abstract_params())
    got = jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)), params)
    if want != got:
        raise RuntimeError(f"program parameter layout changed: {want} != {got}")


def param_count(cfg: dict) -> int:
    def n(tree):
        return sum(math.prod(v[0]) if isinstance(v, tuple) else n(v)
                   for v in tree.values())
    return n(architecture(cfg).param_layout(cfg))
