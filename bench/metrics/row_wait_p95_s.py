"""Engine loop: 95th percentile over the rows of the relQueries due in the
window of the first prefill dispatch less admission (age at the stop for a
row never dispatched), from the engine's wall-clock stamps."""
from harness import phases


def read(ctx):
    w = phases.row_waits(ctx.window)
    return ctx.client.percentile(w, 95) if w else None
