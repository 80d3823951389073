"""Plain float32 forward pass of the dense Qwen decoder (Qwen2.5 and Qwen3).

Written from the published model description, independent of the program:
RMSNorm, grouped-query attention with rotary embeddings (``rotate_half``
convention, base ``rope_theta``), QKV bias where the config has it
(Qwen2.5), per-head RMSNorm of queries and keys before the rotation where it
has qk-norm (Qwen3), SwiGLU MLP, tied or untied output head. No cache, no
kernels, no batching tricks: every row is recomputed from its first token.

It reads the weights the benchmark made, in their stored layout (layers
stacked on axis 0 behind a group axis of 1, query heads as [kv_head,
q_per_kv]); two conventions of that layout are followed here and noted as
departures from the published parameterisation: a norm's stored parameter
``s`` is the offset of its weight, ``weight = 1 + s``, and weights are stored
as [in, out]. Activations, norms, softmax and accumulation are float32. A
weight stored in bfloat16 enters each product exactly: the activation is
split into three bfloat16 parts and each part's product accumulates in
float32, which is the float32 product to float32 rounding. ``quant="fp8"``
is the control: every linear layer's activation and weight rounded to
float8 e4m3 (per-row and per-output-channel scales) before its product.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _split3(x):
    """x (float32) as three bfloat16 parts whose sum is x to float32 rounding."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _fp8(x, axis):
    """Round x to float8 e4m3 with one scale per slice along ``axis``;
    returns (values exactly representable in bfloat16, scale)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return q, s


def linear(x, w, quant: Optional[str]):
    """x [..., in] float32 times w [in, out] (stored dtype) in float32."""
    if quant == "fp8":
        xq, sx = _fp8(x, axis=-1)
        wq, sw = _fp8(w.astype(jnp.float32), axis=0)
        y = jnp.einsum("...i,io->...o", xq, wq,
                       preferred_element_type=jnp.float32)
        return y * sx * sw[0]
    if w.dtype == jnp.float32:
        return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)
    return sum(jnp.einsum("...i,io->...o", p, w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
               for p in _split3(x))


def rmsnorm(x, s, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + s.astype(jnp.float32))


def rope(x, pos, theta):
    """x [R, T, heads, hd]; rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def layer_weights(blocks, l):
    """Layer ``l``'s leaves of the stacked blocks (axis 0, then the group
    axis of 1)."""
    return {n: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)[0]
            for n, a in blocks.items()}


def attention(cfg, quant, x, lens, w):
    """One layer's attention block over rows x [R, T, D] (float32), its
    residual added: pre-norm, q/k/v (bias, qk-norm where the weights have
    them), rotary embeddings, causal softmax attention over each row's own
    length, output projection."""
    R, T, D = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], float(cfg["rms_norm_eps"])
    rep = H // KV
    pos = jnp.broadcast_to(jnp.arange(T), (R, T))

    h = rmsnorm(x, w["ln1"], eps)
    q = linear(h, w["wq"].reshape(D, H * hd), quant).reshape(R, T, H, hd)
    k = linear(h, w["wk"].reshape(D, KV * hd), quant).reshape(R, T, KV, hd)
    v = linear(h, w["wv"].reshape(D, KV * hd), quant).reshape(R, T, KV, hd)
    if "bq" in w:
        q = q + w["bq"].reshape(H, hd).astype(jnp.float32)
        k = k + w["bk"].astype(jnp.float32)
        v = v + w["bv"].astype(jnp.float32)
    if "q_norm" in w:
        q = rmsnorm(q, w["q_norm"], eps)
        k = rmsnorm(k, w["k_norm"], eps)
    q = rope(q, pos, float(cfg["rope_theta"]))
    k = rope(k, pos, float(cfg["rope_theta"]))
    k = jnp.repeat(k, rep, axis=2)          # query head h reads kv head h // rep
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    keep = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None] & \
        (jnp.arange(T)[None, None, None, :] < lens[:, None, None, None])
    s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rhqk,rkhd->rqhd", p, v, precision=HIGHEST)
    return x + linear(o.reshape(R, T, H * hd), w["wo"].reshape(H * hd, D), quant)


def swiglu(h, w_gate, w_up, w_down, quant):
    """SwiGLU MLP of h [..., D] (float32) with weights stored [in, out]."""
    g = linear(h, w_gate, quant)
    u = linear(h, w_up, quant)
    return linear(jax.nn.silu(g) * u, w_down, quant)


def decoder_layer(cfg, quant, x, lens, w):
    """One dense Qwen decoder layer: attention, then the SwiGLU MLP."""
    x = attention(cfg, quant, x, lens, w)
    h = rmsnorm(x, w["ln2"], float(cfg["rms_norm_eps"]))
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)


@partial(jax.jit, static_argnames=("cfg_items", "quant", "layer"))
def _layer_jit(x, lens, blocks, l, *, cfg_items, quant, layer):
    return layer(dict(cfg_items), quant, x, lens, layer_weights(blocks, l))


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _embed_jit(embed, tokens, *, cfg_items, quant):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _head_jit(x, rows, cols, params_head, *, cfg_items, quant):
    cfg = dict(cfg_items)
    h = rmsnorm(x[rows, cols], params_head["final_norm"],
                float(cfg["rms_norm_eps"]))
    w = params_head["lm_head"] if "lm_head" in params_head \
        else params_head["embed"].T
    return linear(h, w, quant)


def _cfg_items(cfg: dict) -> Tuple:
    """The configuration's scalar keys, as a static argument of the jitted
    steps."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if v is None or isinstance(v, (bool, int, float, str))))


def logits_at(cfg: dict, params, seqs: Sequence[Sequence[int]],
              reads: Sequence[Sequence[int]], *, quant: Optional[str] = None,
              tokens_per_block: int = 4096, layer=decoder_layer
              ) -> List[jax.Array]:
    """Logits [len(reads[i]), vocab] (float32, on the device) at positions
    ``reads[i]`` of each token sequence ``seqs[i]``. Rows run in blocks of
    about ``tokens_per_block`` padded tokens, layer by layer; ``layer(cfg,
    quant, x, lens, w)`` is one layer over rows x with that layer's leaves
    w (another architecture's reference passes its own)."""
    items = _cfg_items(cfg)
    T = -(-max(len(s) for s in seqs) // 64) * 64
    per = max(1, tokens_per_block // T)
    head = {k: params[k] for k in ("final_norm", "embed", "lm_head")
            if k in params}
    blocks = params["blocks"]
    out: List[jax.Array] = [None] * len(seqs)
    for b0 in range(0, len(seqs), per):
        idx = list(range(b0, min(len(seqs), b0 + per)))
        toks = np.zeros((per, T), np.int32)
        lens = np.ones((per,), np.int32)
        for j, i in enumerate(idx):
            toks[j, :len(seqs[i])] = seqs[i]
            lens[j] = len(seqs[i])
        x = _embed_jit(params["embed"], jnp.asarray(toks), cfg_items=items,
                       quant=quant)
        lens_d = jnp.asarray(lens)
        for l in range(cfg["num_hidden_layers"]):
            x = _layer_jit(x, lens_d, blocks, jnp.int32(l), cfg_items=items,
                           quant=quant, layer=layer)
        rows = np.concatenate([np.full(len(reads[i]), j, np.int32)
                               for j, i in enumerate(idx)])
        cols = np.concatenate([np.asarray(reads[i], np.int32) for i in idx])
        lg = _head_jit(x, jnp.asarray(rows), jnp.asarray(cols), head,
                       cfg_items=items, quant=quant)
        at = 0
        for i in idx:
            out[i] = lg[at:at + len(reads[i])]
            at += len(reads[i])
    return out
