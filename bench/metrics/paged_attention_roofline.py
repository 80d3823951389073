"""Kernels: paged_attention's share of its roofline, in %. The least time
of the work decode attention needs (each row's context K/V, q and o; no
pages past the context, no pad rows), per tick and attention layer, over
the kernel's summed device time in the trace."""
from harness import work


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    if t is None or p is None or not t["kernel_s"].get("paged_attention"):
        return None
    layers = work.attention_layers(ctx.cfg)
    need = sum(layers * work.least_time(*work.decode_attention(ctx.cfg, tk.decode_contexts), p)
               for tk in ctx.window.ticks if tk.decode_contexts)
    return need / t["kernel_s"]["paged_attention"] * 100.0
