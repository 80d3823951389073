"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick of the roofline and MFU metrics: a kernel's share of
its roofline is the least time the chip could take for the work below,
max(flops / peak flops, bytes / peak bandwidth), over the kernel's summed
device time in the trace. Work that an implementation adds (pages fetched
past a sequence's context, the masked half of causal attention, pad rows of
a bucket) is not needed and is not counted.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

from harness import model

# op kinds (trace names without their instance suffix) of each kernel
KERNEL_OPS = {"paged_attention": ("paged_attention",),
              "flash_prefill": ("flash_prefill",)}


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], DTYPE_BYTES[cfg["torch_dtype"]])


def decode_attention(cfg: dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """One layer's decode attention over rows with these context lengths
    (the new token included): q.k and p.v over the context, reading the
    context's K and V once and q and o once."""
    H, KV, hd, b = _dims(cfg)
    c = np.asarray(list(contexts), np.float64)
    flops = float(np.sum(4.0 * H * hd * c))
    nbytes = float(np.sum(2.0 * KV * hd * c * b) + c.size * 2.0 * H * hd * b)
    return flops, nbytes


def prefill_attention(cfg: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """One layer's causal prefill attention over rows of these lengths: the
    n(n+1)/2 query-key pairs of each row, reading q, k, v and writing o once."""
    H, KV, hd, b = _dims(cfg)
    n = np.asarray(list(lengths), np.float64)
    flops = float(np.sum(4.0 * H * hd * n * (n + 1) / 2.0))
    nbytes = float(np.sum((2.0 * H + 2.0 * KV) * hd * n * b))
    return flops, nbytes


def attention_layers(cfg: dict) -> int:
    """The layers that run attention, by the configuration's architecture."""
    return model.architecture(cfg).attention_layers(cfg)


def model_flops(cfg: dict, prefill_lengths: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """Model FLOPs of whole prompts and of decoded tokens: the matmuls every
    token passes through (active experts only, by the architecture), causal
    attention on the layers that run it, and one row of output logits per
    prompt and per decoded token."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    arch = model.architecture(cfg)
    n = np.asarray(list(prefill_lengths), np.float64)
    c = np.asarray(list(decode_contexts), np.float64)
    flops = 2.0 * arch.matmul_params_per_token(cfg) * (n.sum() + c.size)
    layers = arch.attention_layers(cfg)
    if layers:          # an attention-free model has no head sizes
        flops += layers * (prefill_attention(cfg, n)[0] +
                           decode_attention(cfg, c)[0])
    return float(flops + 2.0 * D * V * (n.size + c.size))


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
