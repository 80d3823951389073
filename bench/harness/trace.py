"""Profiler trace to device busy time, kernel time, top ops and idle gaps.

Two stages, so the second can be checked on a small recorded trace:
``extract`` reads an ``.xplane.pb`` with JAX's ``ProfileData`` into plain
lists (device ops of each device's "XLA Ops" line, and the benchmark's own
``bench.*`` host spans), and ``reduce`` turns those lists into numbers.
Both clocks of one trace share an origin, so a device gap can be named by
the host span it falls in. A trace with no device plane (the CPU's) holds
its ops on the host's threads, as events that carry an ``hlo_op``; those
stand in for the device's ops there.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)


def load(log_dir):
    """JAX's ``ProfileData`` of the one trace under ``log_dir``."""
    import jax

    paths = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    return jax.profiler.ProfileData.from_file(paths[0])


def extract(log_dir) -> dict:
    """{'devices': {plane: [Event]}, 'host': [Event]} from the one trace
    under ``log_dir``."""
    return from_profile(load(log_dir))


def host_ops(pd) -> Dict[str, list]:
    """{'/host:CPU:<ordinal>': [(name, start_ns, duration_ns, hlo_module)]}:
    the XLA ops that ran on the host's threads, where the trace has no
    device plane; empty where it has one."""
    if any(plane.name.startswith("/device:") for plane in pd.planes):
        return {}
    out: Dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    st = dict(e.stats)
                    if "hlo_op" in st:
                        key = f"/host:CPU:{st.get('device_ordinal', 0)}"
                        out.setdefault(key, []).append(
                            (e.name, e.start_ns, e.duration_ns,
                             st.get("hlo_module", "")))
    return out


def from_profile(pd) -> dict:
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    for plane, ops in host_ops(pd).items():
        devices[plane] = [op[:3] for op in ops]
    return {"devices": devices, "host": host}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


# ops that contain other ops on the same trace line (their children are
# counted themselves)
CONTAINERS = ("while", "conditional", "call")


def op_kind(name: str) -> str:
    """An op's kind from its trace name, which is the op's HLO text:
    '%copy_bitcast_fusion.54 = bf16[...] fusion(...)' -> 'copy_bitcast_fusion'.
    Only the op's own name counts, never the operands it reads."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ", 1)[0].lstrip("%"))


def reduce(ex: dict, kernels: Dict[str, Sequence[str]], top: int = 10) -> dict:
    """Numbers of the traced window (the ``bench.window`` span): busy
    seconds averaged over devices, seconds of each kernel (ops whose kind is
    one of its names), seconds of every op kind (``op_s``; containers left
    out), the ``top`` of those kinds by time, and the ``top`` longest idle
    gaps of the first device, each named by the innermost benchmark span
    that covers the gap's midpoint."""
    win = [e for e in ex["host"] if e[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    if not ex["devices"]:
        raise RuntimeError("trace holds no device ops")
    busy = []
    kernel_s = {k: 0.0 for k in kernels}
    by_kind: Dict[str, float] = defaultdict(float)
    for plane in sorted(ex["devices"]):
        clipped = list(_clip(ex["devices"][plane], lo, hi))
        merged = _union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, a, b in clipped:
            kind = op_kind(name)
            if kind not in CONTAINERS:
                by_kind[kind] += (b - a) / 1e9
            for k, kinds in kernels.items():
                if kind in kinds:
                    kernel_s[k] += (b - a) / 1e9
    n_dev = len(ex["devices"])
    first = _union([(a, b) for _, a, b in
                    _clip(ex["devices"][sorted(ex["devices"])[0]], lo, hi)])
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [e for e in ex["host"] if e[0] != WINDOW_SPAN]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        cover = [e for e in spans if e[1] <= mid <= e[1] + e[2]]
        name = min(cover, key=lambda e: e[2])[0] if cover else "none"
        named.append([name, (b - a) / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n_dev,
        "kernel_s": {k: v / n_dev for k, v in kernel_s.items()},
        "op_s": {k: v / n_dev for k, v in by_kind.items()},
        "device_ops": [[k, v / n_dev] for k, v in
                       sorted(by_kind.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }
