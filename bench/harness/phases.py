"""The paper's three relQuery phases on the wall clock, from the program's
own stamps.

The engine stamps each request (``time.perf_counter``, the clock the client
uses) when it is admitted, when its prefill is first dispatched, when its
prompt completes (the last time, after a preemption) and when it finishes.
For a relQuery: waiting is its first dispatch less its admission, core
running its last prompt completion less its first dispatch, tail running
its last finish less its last prompt completion; the three sum to its last
finish less its admission. A program without the stamps gives nothing.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

STAMPS = ("t_admit", "t_prefill_dispatch", "t_prefill_done", "t_finish")


def _stamps(request):
    return tuple(getattr(request, s, None) for s in STAMPS)


def relq_phases(window) -> List[Tuple[float, float, float]]:
    """(waiting, core, tail) seconds of each relQuery due in the window whose
    rows all finished by the stop."""
    groups: Dict[str, list] = {}
    for row in window.rows.values():
        if row.qid in window.schedule:
            groups.setdefault(row.qid, []).append(_stamps(row.request))
    out = []
    for qid in window.schedule:
        st = groups.get(qid, [])
        if not st or any(None in s for s in st):
            continue
        admit = min(s[0] for s in st)
        first = min(s[1] for s in st)
        done = max(s[2] for s in st)
        finish = max(s[3] for s in st)
        out.append((first - admit, done - first, finish - done))
    return out


def row_waits(window) -> List[float]:
    """First prefill dispatch less admission, for every admitted row of the
    relQueries due in the window (age at the stop for one never
    dispatched)."""
    out = []
    for row in window.rows.values():
        if row.qid not in window.schedule:
            continue
        admit, first = _stamps(row.request)[:2]
        if admit is None:
            continue
        out.append((first if first is not None and first <= window.t_stop
                    else window.t_stop) - admit)
    return out
