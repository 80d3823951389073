"""The dense Qwen decoder (Qwen2.5, Qwen3) as the program builds it.

A configuration names this module with ``"architecture": "arch/qwen.py"``.
It maps the published ``config.json`` keys, plus ``serve`` (qk-norm, QKV
bias: what a dense HF config states through its ``model_type``), onto the
program's ``ModelConfig`` and parameter tree, and counts the matmul
parameters a token passes through and the layers that run attention.
"""
from __future__ import annotations

from harness.model import BIAS_STD, NORM_STD


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    serve = cfg["serve"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_kind="full", qk_norm=serve["qk_norm"],
        qkv_bias=serve["qkv_bias"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], source=cfg["source"])


def attention_blocks(cfg: dict) -> dict:
    """name -> (shape, std) of the attention leaves and the two norms of a
    stacked decoder layer; query heads packed as [kv_head, q_per_kv], query
    head h = kv * q_per_kv + j."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    Qp = H // KV
    blocks = {
        "ln1": ((L, 1, D), NORM_STD), "ln2": ((L, 1, D), NORM_STD),
        "wq": ((L, 1, D, KV, Qp, hd), D ** -0.5),
        "wk": ((L, 1, D, KV, hd), D ** -0.5),
        "wv": ((L, 1, D, KV, hd), D ** -0.5),
        "wo": ((L, 1, KV, Qp, hd, D), (H * hd) ** -0.5),
    }
    if cfg["serve"]["qkv_bias"]:
        blocks.update(bq=((L, 1, KV, Qp, hd), BIAS_STD),
                      bk=((L, 1, KV, hd), BIAS_STD),
                      bv=((L, 1, KV, hd), BIAS_STD))
    if cfg["serve"]["qk_norm"]:
        blocks.update(q_norm=((L, 1, hd), NORM_STD),
                      k_norm=((L, 1, hd), NORM_STD))
    return blocks


def with_head(cfg: dict, blocks: dict) -> dict:
    """The whole tree around ``blocks``: embedding, final norm, and the
    output head unless it is tied to the embedding."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    out = {"embed": ((V, D), D ** -0.5), "final_norm": ((D,), NORM_STD),
           "blocks": blocks}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((D, V), D ** -0.5)
    return out


def param_layout(cfg: dict) -> dict:
    """name -> (shape, std) of every leaf, in the program's single-device
    layout: layers stacked on axis 0 with a group axis of 1."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    F = cfg["intermediate_size"]
    blocks = attention_blocks(cfg)
    blocks.update(w_gate=((L, 1, D, F), D ** -0.5),
                  w_up=((L, 1, D, F), D ** -0.5),
                  w_down=((L, 1, F, D), F ** -0.5))
    return with_head(cfg, blocks)


def attention_matmul_params(cfg: dict) -> int:
    """One attention layer's projection parameters: q, k, v and o."""
    D = cfg["hidden_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return D * H * hd + 2 * D * KV * hd + H * hd * D


def matmul_params_per_token(cfg: dict) -> int:
    """Matmul parameters one token passes through, over every layer (the
    output head is counted apart)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (attention_matmul_params(cfg) + 3 * D * F)


def attention_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]
