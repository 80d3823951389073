"""Engine loop: the core running phase, mean over the relQueries due in the
window (all rows finished) of the last prompt completion less the first
prefill dispatch, from the engine's wall-clock stamps on each request."""
import statistics

from harness import phases


def read(ctx):
    p = phases.relq_phases(ctx.window)
    return statistics.fmean(c for _, c, _ in p) if p else None
