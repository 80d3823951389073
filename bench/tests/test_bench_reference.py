"""The float32 reference against the program's own forward pass, at a small
size on the CPU, for both paths: Qwen3 (qk-norm, tied head) and Qwen2.5
(QKV bias, untied head). Both are reached as every run reaches them: the
program's model through the configuration's architecture module, the
reference through the module its ``reference`` key names."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import model as hmodel

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(params=["tiny-qwen3", "tiny-qwen2.5"])
def setup(request):
    cfg = hmodel.load_config(request.param, DATA)
    cfg["torch_dtype"] = "float32"
    params = hmodel.make_params(cfg, 2 ** 35 + 17)
    prog = hmodel.build(cfg)
    hmodel.check_layout(params, prog)
    return cfg, params, prog


def program_logits(prog, params, toks):
    """Every position's logits through the program's own forward pass."""
    B, S = toks.shape
    emb = prog.embed_tokens(params, jnp.asarray(toks))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.default_matmul_precision("highest"):
        h, _, _ = prog.forward_hidden(params, emb, pos)
        return np.asarray(prog.logits(params, h))


def test_reference_matches_the_program_in_float32(setup):
    cfg, params, prog = setup
    rng = np.random.RandomState(0)
    lens = [37, 64, 5]
    seqs = [list(rng.randint(2, cfg["vocab_size"], n)) for n in lens]
    reads = [list(range(n)) for n in lens]
    ref = hmodel.reference(cfg).logits_at(cfg, params, seqs, reads,
                                          tokens_per_block=128)
    for s, r in zip(seqs, ref):
        want = program_logits(prog, params, np.asarray([s], np.int32))[0]
        np.testing.assert_allclose(np.asarray(r), want, rtol=2e-4, atol=2e-4)


def test_reference_sees_each_architectural_part(setup):
    """Dropping qk-norm or the biases moves the logits: the reference is
    not blind to the part that distinguishes each path."""
    cfg, params, prog = setup
    seqs = [[1, 5, 9, 13, 17, 21, 25, 29]]
    reads = [list(range(8))]
    logits_at = hmodel.reference(cfg).logits_at
    base = np.asarray(logits_at(cfg, params, seqs, reads)[0])
    name = "q_norm" if cfg["serve"]["qk_norm"] else "bq"
    broken = jax.tree.map(lambda a: a, params)
    broken["blocks"][name] = jnp.zeros_like(broken["blocks"][name]) + 0.7
    moved = np.asarray(logits_at(cfg, broken, seqs, reads)[0])
    assert np.max(np.abs(moved - base)) > 1e-2


def test_fp8_control_departs_more_than_rounding(setup):
    cfg, params, prog = setup
    rng = np.random.RandomState(1)
    seqs = [list(rng.randint(2, cfg["vocab_size"], 48))]
    reads = [list(range(48))]
    logits_at = hmodel.reference(cfg).logits_at
    ref = np.asarray(logits_at(cfg, params, seqs, reads)[0])
    ctl = np.asarray(logits_at(cfg, params, seqs, reads, quant="fp8")[0])
    err = np.max(np.abs(ctl - ref)) / np.max(np.abs(ref))
    assert 1e-3 < err < 0.5
