"""Ticks that ran a batch in the traced window, counted from the program's
own ``relserve.tick`` spans, which this reader asks to be on."""
SPANS = True


def read(ctx):
    if ctx.program is None:
        return None
    n = sum(1 for name, _, _, args in ctx.program["spans"]
            if name == "relserve.tick" and "kind" in args)
    return n or None
