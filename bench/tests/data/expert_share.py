"""Share of the window's device time that the decode step spends in its
``mlp`` scope (the experts, in a sparse-expert model), in %: device seconds
of ``decode_step/mlp`` from the program's side of the trace over the
seconds of every op kind."""


def read(ctx):
    if ctx.program is None or ctx.trace is None:
        return None
    mlp = ctx.program["scope_s"].get(("decode_step", "mlp"), 0.0)
    total = sum(ctx.trace["op_s"].values())
    return mlp / total * 100.0 if mlp and total else None
