"""Engine loop: the waiting phase, mean over the relQueries due in the
window (all rows finished) of the first prefill dispatch less admission,
from the engine's wall-clock stamps on each request."""
import statistics

from harness import phases


def read(ctx):
    p = phases.relq_phases(ctx.window)
    return statistics.fmean(w for w, _, _ in p) if p else None
