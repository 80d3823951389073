"""Kernels: flash_prefill's share of its roofline, in %. The least time of
the work causal prefill attention needs (each row's n(n+1)/2 pairs over its
own length, q, k, v and o once; no masked half, no padding), per tick and
attention layer, over the kernel's summed device time in the trace."""
from harness import work


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    if t is None or p is None or not t["kernel_s"].get("flash_prefill"):
        return None
    layers = work.attention_layers(ctx.cfg)
    need = sum(layers * work.least_time(*work.prefill_attention(ctx.cfg, tk.prefill_lengths), p)
               for tk in ctx.window.ticks if tk.prefill_lengths)
    return need / t["kernel_s"]["flash_prefill"] * 100.0
