"""Engine loop: the tail running phase, mean over the relQueries due in the
window (all rows finished) of the last finish less the last prompt
completion, from the engine's wall-clock stamps on each request."""
import statistics

from harness import phases


def read(ctx):
    p = phases.relq_phases(ctx.window)
    return statistics.fmean(t for _, _, t in p) if p else None
