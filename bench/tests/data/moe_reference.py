"""Plain float32 forward pass of the sparse-expert decoder: the Qwen
reference's attention, then a softmax router whose top ``num_experts_per_tok``
probabilities, renormalised to sum to one, weight those experts' SwiGLU
outputs. Every expert runs on every token and the weights of the others
are zero: no capacity, no dispatch."""
import jax
import jax.numpy as jnp

from reference import qwen


def experts(cfg, quant, h, w):
    probs = jax.nn.softmax(qwen.linear(h, w["router"], quant), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for e in range(cfg["num_experts"]):
        gate = jnp.sum(jnp.where(top_i == e, top_w, 0.0), axis=-1)
        out = out + gate[..., None] * qwen.swiglu(
            h, w["w_gate"][e], w["w_up"][e], w["w_down"][e], quant)
    return out


def layer(cfg, quant, x, lens, w):
    x = qwen.attention(cfg, quant, x, lens, w)
    h = qwen.rmsnorm(x, w["ln2"], float(cfg["rms_norm_eps"]))
    return x + experts(cfg, quant, h, w)


def logits_at(cfg, params, seqs, reads, *, quant=None, tokens_per_block=4096):
    return qwen.logits_at(cfg, params, seqs, reads, quant=quant,
                          tokens_per_block=tokens_per_block, layer=layer)
