"""The program's own spans, and the device's time by program and by scope,
from the same profiler trace as ``harness.trace``.

``extract`` reads the one ``.xplane.pb`` into plain lists: the host spans
named ``relserve.*`` (the program's, with their arguments) and ``bench.*``
(the benchmark's), the device's program executions (its "XLA Modules"
line), and each device op with the program whose execution holds it. A
TPU trace's ops carry no name metadata, so an op's scope comes from the
compiled HLO of its program (``hlo_scopes``): the ``jax.named_scope`` names
in the instruction's ``op_name``, where all of the program's variants
agree on it. ``reduce`` turns those lists into
readings of the ``bench.window`` span, so it can be checked on a small
synthetic trace. A program without the spans or scopes gives empty
readings, not an error. The scope names are the program's own
(``repro.engine.tracing.SCOPES``) where it lists them.
"""
from __future__ import annotations

import bisect
import re
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from harness.trace import (CONTAINERS, OPS_LINE, WINDOW_SPAN, _clip, _union,
                           host_ops, load, op_kind)

PROGRAM_PREFIX = "relserve."
HOST_PREFIXES = (PROGRAM_PREFIX, "bench.")
MODULES_LINE = "XLA Modules"


def _program_scopes() -> Tuple[str, ...]:
    """The program's named scopes: its own list where it has one, else the
    six of ``models/transformer.py``."""
    try:
        from repro.engine.tracing import SCOPES as scopes
    except ImportError:
        scopes = ("embed", "qkv", "kv_write", "attn", "mlp", "logits")
    return tuple(scopes)


SCOPES = _program_scopes()
TICK = PROGRAM_PREFIX + "tick"
AMBIGUOUS = "?"   # an instruction whose variants disagree on its scope
SYNC_SPANS = (PROGRAM_PREFIX + "prefill_sync", PROGRAM_PREFIX + "decode_sync")

Span = Tuple[str, float, float, dict]               # name, start, dur, args
Op = Tuple[str, float, float, str]                  # name, start, dur, program


def program_name(module: str) -> str:
    """'jit_decode_step(12)' or 'jit_decode_step' -> 'decode_step'."""
    return re.sub(r"^jit_", "", re.sub(r"\(\d+\)$", "", module or ""))


def scope_of(path: str) -> str:
    """The innermost program scope in an op's name metadata:
    'jit(decode_step)/attn/paged_attention' -> 'attn'; '' when it has none."""
    for part in reversed((path or "").split("/")):
        if part in SCOPES:
            return part
    return ""


def extract(log_dir) -> dict:
    """{'spans': [Span], 'modules': {plane: [(program, start, dur)]},
    'ops': {plane: [Op]}} from the one trace under ``log_dir``."""
    return from_profile(load(log_dir))


def from_profile(pd) -> dict:
    spans: List[Span] = []
    modules: Dict[str, list] = {}
    ops: Dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        spans.append((e.name, e.start_ns, e.duration_ns,
                                      dict(e.stats) if e.name.startswith(
                                          PROGRAM_PREFIX) else {}))
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            mods = [(program_name(e.name), e.start_ns, e.duration_ns)
                    for e in lines[MODULES_LINE].events] \
                if MODULES_LINE in lines else []
            modules[plane.name] = mods
            if OPS_LINE in lines:
                ops[plane.name] = _ops(lines[OPS_LINE].events, mods)
    for plane, events in host_ops(pd).items():
        ops[plane] = [(n, s, d, program_name(m)) for n, s, d, m in events]
    return {"spans": spans, "modules": modules, "ops": ops}


def _ops(events, mods) -> List[Op]:
    """Each op with the program whose execution (one device runs one at a
    time) holds the op's midpoint; '' when none does."""
    mods = sorted(mods, key=lambda m: m[1])
    starts = [s for _, s, _ in mods]
    out = []
    for e in events:
        mid = e.start_ns + e.duration_ns / 2
        i = bisect.bisect_right(starts, mid) - 1
        held = i >= 0 and mid <= mods[i][1] + mods[i][2]
        out.append((e.name, e.start_ns, e.duration_ns,
                    mods[i][0] if held else ""))
    return out


def hlo_texts(executor) -> Dict[str, List[str]]:
    """Compiled HLO texts of every program the executor holds (its
    attributes that are compiled executables, or dicts of them: one per
    shape variant), keyed by the program's name as the trace gives it."""
    out: Dict[str, List[str]] = defaultdict(list)
    for value in vars(executor).values():
        fns = value.values() if isinstance(value, dict) else [value]
        for f in fns:
            if hasattr(f, "as_text") and hasattr(f, "cost_analysis"):
                text = f.as_text()
                m = re.match(r"HloModule ([\w.\-]+)", text)
                out[program_name(m.group(1) if m else "")].append(text)
    return dict(out)


def hlo_scopes(hlo_texts: Dict[str, Sequence[str]]
               ) -> Dict[Tuple[str, str], str]:
    """(program, instruction) -> the instruction's ``op_name``, from the
    compiled HLO texts of each program's variants (one per shape). A trace
    op names its program and instruction, not the variant, so an
    instruction that two variants place in different scopes maps to
    ``AMBIGUOUS``: ``reduce`` counts its time under scope '?' rather than
    crediting either."""
    out = {}
    for program, texts in hlo_texts.items():
        for text in texts:
            for name, path in op_names(text):
                key = (program, name)
                seen = out.setdefault(key, path)
                if seen != AMBIGUOUS and scope_of(seen) != scope_of(path):
                    out[key] = AMBIGUOUS
    return out


INSTRUCTION = re.compile(r"%?([\w.\-]+) = ")


def op_names(text: str):
    """(instruction, op_name) of each line of an HLO text whose metadata
    has an ``op_name``. Line by line with plain string search: a 28-layer
    decode step's text is some 3 MB, and a pattern over the whole text that
    fails on most lines costs seconds for each."""
    for line in text.splitlines():
        meta = line.find("metadata={")
        while meta >= 0:        # also ``kernel_metadata={}`` and the like
            end = line.find("}", meta)
            at = line.find('op_name="', meta, len(line) if end < 0 else end)
            if at >= 0:
                m = INSTRUCTION.search(line, 0, meta)
                if m:
                    at += len('op_name="')
                    yield m.group(1), line[at:line.index('"', at)]
                break
            meta = line.find("metadata={", meta + 1)


def instruction(name: str) -> str:
    """An op's instruction name from its trace name (its HLO text)."""
    return name.split(" = ", 1)[0].lstrip("%")


def window_of(ex: dict) -> Tuple[float, float]:
    win = [s for s in ex["spans"] if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    return win[0][1], win[0][1] + win[0][2]


def reduce(ex: dict, scopes: Optional[Dict[Tuple[str, str], str]] = None,
           top: int = 10) -> dict:
    """Readings of the traced window, averaged over devices:

    - ``by_program``: device seconds and executions of each program;
    - ``by_scope``: device seconds of the ``top`` program/scope pairs;
      ``scope_s``: of every pair, keyed by (program, scope);
    - ``owners``: for each of the ``top`` op kinds, the seconds of each
      program/scope that ran it;
    - ``idle_gaps``: the ``top`` longest idle gaps of the first device, each
      named by the innermost program span covering its midpoint;
    - ``host_ms_per_tick``: mean ms of the ticks inside the window, less
      their host-blocked ``*_sync`` children;
    - ``spans``: the program's ``relserve.*`` spans inside the window, with
      their arguments.
    """
    lo, hi = window_of(ex)
    scopes = scopes or {}
    n_dev = max(1, len(ex["ops"]))
    by_program: Dict[str, float] = defaultdict(float)
    by_scope: Dict[str, float] = defaultdict(float)
    scope_s: Dict[Tuple[str, str], float] = defaultdict(float)
    by_kind: Dict[str, float] = defaultdict(float)
    owners: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for plane, events in ex["ops"].items():
        for name, s, d, program in events:
            a, b = max(s, lo), min(s + d, hi)
            kind = op_kind(name)
            if b <= a or kind in CONTAINERS:
                continue
            sec = (b - a) / 1e9 / n_dev
            path = scopes.get((program, instruction(name)), "")
            scope = AMBIGUOUS if path == AMBIGUOUS else scope_of(path)
            where = f"{program or '?'}/{scope or '-'}"
            by_program[program or "?"] += sec
            by_scope[where] += sec
            scope_s[program or "?", scope or "-"] += sec
            by_kind[kind] += sec
            owners[kind][where] += sec
    calls: Dict[str, int] = defaultdict(int)
    module_s: Dict[str, float] = defaultdict(float)
    for plane, mods in ex["modules"].items():
        for program, s, d in mods:
            if lo <= s + d / 2 <= hi:
                calls[program] += 1
                module_s[program] += d / 1e9
    top_kinds = sorted(by_kind, key=lambda k: -by_kind[k])[:top]
    return {
        "by_program": [[p, v, calls.get(p, 0) / n_dev] for p, v in
                       sorted(by_program.items(), key=lambda kv: -kv[1])],
        "scope_s": dict(scope_s),
        "module_s": {p: v / n_dev for p, v in module_s.items()},
        "calls": {p: c / n_dev for p, c in calls.items()},
        "by_scope": [[k, v] for k, v in
                     sorted(by_scope.items(), key=lambda kv: -kv[1])[:top]],
        "owners": {k: sorted(([w, v] for w, v in owners[k].items()),
                             key=lambda wv: -wv[1])[:3] for k in top_kinds},
        "idle_gaps": idle_gaps(ex, lo, hi, top),
        "host_ms_per_tick": host_ms_per_tick(ex["spans"], lo, hi),
        "spans": [sp for sp in ex["spans"] if sp[0].startswith(PROGRAM_PREFIX)
                  and lo <= sp[1] and sp[1] + sp[2] <= hi],
    }


def idle_gaps(ex: dict, lo: float, hi: float, top: int) -> list:
    """The ``top`` longest idle gaps of the first device in [lo, hi], each
    named by the innermost program span that covers its midpoint."""
    if not ex["ops"]:
        return []
    first = _union([(a, b) for _, a, b in _clip(
        [(o[0], o[1], o[2]) for o in ex["ops"][sorted(ex["ops"])[0]]], lo, hi)])
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [s for s in ex["spans"] if s[0].startswith(PROGRAM_PREFIX)]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        out.append([min(cover, key=lambda s: s[2])[0] if cover else "none",
                    (b - a) / 1e9])
    return out


def ticks(spans: Sequence[Span], lo: float, hi: float) -> List[dict]:
    """The ``relserve.tick`` spans that lie inside [lo, hi] and ran a batch
    (the engine tags those with ``kind``; a tick that found no batch is
    left out), each with its children (the program spans inside it, in
    start order)."""
    out = []
    inner = sorted((s for s in spans if s[0].startswith(PROGRAM_PREFIX)
                    and s[0] != TICK), key=lambda s: s[1])
    for name, s, d, args in sorted((t for t in spans if t[0] == TICK),
                                   key=lambda t: t[1]):
        if s < lo or s + d > hi or "kind" not in args:
            continue
        kids = [c for c in inner if s <= c[1] and c[1] + c[2] <= s + d]
        out.append({"start": s, "dur": d, "args": args, "children": kids})
    return out


def host_ms_per_tick(spans: Sequence[Span], lo: float, hi: float
                     ) -> Optional[float]:
    """Mean over the window's ticks of the tick span less its ``*_sync``
    children (the host blocked on the device), in ms."""
    ts = ticks(spans, lo, hi)
    if not ts:
        return None
    return statistics.fmean(
        (t["dur"] - sum(c[2] for c in t["children"] if c[0] in SYNC_SPANS))
        / 1e6 for t in ts)


def device_ms_per_call(red: dict, program: str) -> Optional[float]:
    """Device ms per execution of ``program``: its module events' mean
    duration where the trace has them."""
    n = red["calls"].get(program, 0)
    return red["module_s"][program] * 1e3 / n if n else None


def device_s(red: dict, programs: Sequence[str]) -> float:
    return sum(v for p, v, _ in red["by_program"] if p in programs)


def cost_model_error(samples: Sequence[Tuple[float, float]]) -> Optional[float]:
    """Median over ticks of |predicted - measured| / measured, in %."""
    err = [abs(p - m) / m * 100.0 for p, m in samples if m > 0]
    return statistics.median(err) if err else None
