"""Readings of the Qwen configurations pinned to the values the harness gave
before a configuration brought its own architecture module: the weights
drawn from a seed, the model FLOPs, the kernels' roofline work, and the
reference's logits. A change that moves one of them moves a cell's numbers."""
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import run
from harness import model as hmodel, work
from harness.peaks import peaks_for
from harness.serve import Tick

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 35 + 17

# sha256 (first 16 hex digits) of every leaf's bytes
LEAVES = {
    "tiny-qwen3": {
        "['blocks']['k_norm']": "90b5b8fa94371853",
        "['blocks']['ln1']": "8dc521a3f0b52511",
        "['blocks']['ln2']": "9ad983a9fc58f2dd",
        "['blocks']['q_norm']": "a2f24df2e7e9015f",
        "['blocks']['w_down']": "5b942265bde0e1fb",
        "['blocks']['w_gate']": "2a02f94f07de9603",
        "['blocks']['w_up']": "1f2279dd83daccd4",
        "['blocks']['wk']": "8e7b844b1ff71cf5",
        "['blocks']['wo']": "5af47585ef7d6ce9",
        "['blocks']['wq']": "3bc0b35e64cb445b",
        "['blocks']['wv']": "1ceb1089d6794476",
        "['embed']": "8bc3e6d72c35abc5",
        "['final_norm']": "1a66acd252830892"},
    "tiny-qwen2.5": {
        "['blocks']['bk']": "c7849d4642109e1b",
        "['blocks']['bq']": "68cfe64ffc4a3832",
        "['blocks']['bv']": "924316fd0eae9f08",
        "['blocks']['ln1']": "ea2063328db85df1",
        "['blocks']['ln2']": "5133b2ace25900ba",
        "['blocks']['w_down']": "e5c9453899cd2d0b",
        "['blocks']['w_gate']": "1f2279dd83daccd4",
        "['blocks']['w_up']": "87eb5a58fa34981f",
        "['blocks']['wk']": "1da5c3fb72a4445a",
        "['blocks']['wo']": "3bc0b35e64cb445b",
        "['blocks']['wq']": "1ce6b0c7e327fd89",
        "['blocks']['wv']": "496d81b1508a1ef6",
        "['embed']": "7b33ce6fb6f669e7",
        "['final_norm']": "64bdd4d029ea15b1",
        "['lm_head']": "622885768260f908"},
}

# (prefill lengths, decode contexts) -> model FLOPs of qwen3-1.7b
FLOPS = [
    ([], [1], 3441131520.0),
    ([19, 340, 128], [], 1389745930240.0),
    ([], [20, 300, 511, 64], 13968900096.0),
    ([200, 120, 7], [33, 400, 129, 512, 1], 947273367552.0),
]

# reference logits at (row, read, token) of two fixed sequences
SEQS = [[3 + (7 * i) % 500 for i in range(37)], [11, 5, 9, 400, 2]]
READS = [[0, 17, 36], [1, 4]]
AT = [(0, 2, 7), (0, 1, 300), (1, 1, 11)]
LOGITS = {
    ("tiny-qwen3", None): [-0.3007287383079529, 0.6710326075553894,
                           1.421493649482727],
    ("tiny-qwen3", "fp8"): [-0.1563711017370224, 0.6640121936798096,
                            1.4209803342819214],
    ("tiny-qwen2.5", None): [0.12340189516544342, -0.9666302800178528,
                             -0.7684204578399658],
    ("tiny-qwen2.5", "fp8"): [0.1702546328306198, -0.9750649929046631,
                              -0.6170806884765625],
}


def digest(a) -> str:
    a = np.asarray(a)
    raw = a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)
    return hashlib.sha256(raw.tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_weights_are_drawn_as_before(name):
    cfg = hmodel.load_config(name, DATA)
    params = hmodel.make_params(cfg, SEED)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {jax.tree_util.keystr(k): digest(v) for k, v in flat}
    assert got == LEAVES[name]


@pytest.mark.parametrize("prefill,decode,flops", FLOPS)
def test_model_flops_are_as_before(prefill, decode, flops):
    cfg = hmodel.load_config("qwen3-1.7b")
    assert work.model_flops(cfg, prefill, decode) == flops


def test_param_count_is_as_before():
    assert hmodel.param_count(hmodel.load_config("qwen3-1.7b")) == 1720574976


def ctx(ticks, kernel_s):
    return SimpleNamespace(
        window=SimpleNamespace(ticks=ticks),
        cfg=hmodel.load_config("qwen3-1.7b"), peaks=peaks_for("TPU v5 lite"),
        trace={"kernel_s": kernel_s, "window_s": 1.0, "busy_s": 0.5})


def test_roofline_work_is_as_before():
    metrics = run.BENCH / "metrics"
    dec = [Tick(0.0, [], [300, 100, 17, 512], 0.5), Tick(0.1, [], [301], 0.5)]
    c = ctx(dec, {"paged_attention": 0.01})
    need = sum(work.attention_layers(c.cfg) * work.least_time(
        *work.decode_attention(c.cfg, t.decode_contexts), c.peaks) for t in dec)
    assert need == 0.00017364239316239316
    assert run.load_reader("paged_attention_roofline", metrics)(c) == \
        1.7364239316239316
    pre = ctx([Tick(0.0, [200, 120, 19], [], 0.5)], {"flash_prefill": 0.002})
    assert run.load_reader("flash_prefill_roofline", metrics)(pre) == \
        7.12073846153846
    assert run.load_reader("step_mfu", metrics)(pre) == 0.48917883451776656


@pytest.mark.parametrize("name,quant", sorted(LOGITS, key=str))
def test_reference_logits_are_as_before(name, quant):
    cfg = hmodel.load_config(name, DATA)
    params = hmodel.make_params(cfg, SEED)
    lg = hmodel.reference(cfg).logits_at(cfg, params, SEQS, READS,
                                         quant=quant, tokens_per_block=128)
    got = [float(np.asarray(lg[r])[i, t]) for r, i, t in AT]
    np.testing.assert_allclose(got, LOGITS[name, quant], rtol=1e-5, atol=1e-6)


def test_a_configuration_must_name_its_architecture(tmp_path):
    cfg = json.loads((DATA / "tiny-qwen3.json").read_text())
    del cfg["architecture"]
    (tmp_path / "bare.json").write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="architecture"):
        hmodel.load_config("bare", tmp_path)
