"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the rows of the relQueries due in the window that finished (the drain lets
those with long answers finish), drawn from the seed and holding the row with
the most served tokens and a row of the fullest decode batch, is run through
the configuration's plain float32 reference, each prompt followed by the tokens the client
received. For every served token the gap
is how far its reference logit lies below the reference's best logit at
that position; the widest gap is compared with the configuration's limit.
Greedy decoding serves the program's best token, so a sound run reads only
rounding. The stream itself is checked exactly: a finished row must have
streamed every token the program produced for it, up to its output limit
or an end-of-sequence token.

The control (``control=True``) puts the reference, computed with every
linear layer in float8, in the program's place: at each position of the same
prompts and served tokens, the float8 reference's best token takes the place
of the served token, and its gap in the float32 reference is the widest gap
that the verdict reads, so that a control run reports ``correct`` false.
"""
from __future__ import annotations

import random
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

SAMPLE_TOKENS = 400      # served tokens the sample holds, at the least
EOS = 0


def sample_rows(window, seed: int) -> list:
    """Finished rows of the relQueries due in the window, drawn from
    ``seed``: the one with the most served tokens (then the longest prompt),
    the one decoded in the fullest batch (then the most served tokens), and
    then others until the sample holds ``SAMPLE_TOKENS`` served tokens or
    every such row."""
    done = sorted((r for r in window.rows.values()
                   if r.finished and r.qid in window.schedule),
                  key=lambda r: (r.qid, r.index))
    if not done:
        return []
    out = [max(done, key=lambda r: (len(r.tokens), len(r.prompt)))]
    fullest = max(done, key=lambda r: (r.max_batch, len(r.tokens)))
    if fullest is not out[0]:
        out.append(fullest)
    rest = [r for r in done if all(r is not o for o in out)]
    random.Random(seed).shuffle(rest)
    n = sum(len(r.tokens) for r in out)
    for r in rest:
        if n >= SAMPLE_TOKENS:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def stream_faults(rows) -> int:
    """Rows whose stream differs from the program's output, or that stop
    before their output limit without an end-of-sequence token."""
    bad = 0
    for r in rows:
        produced = list(r.request.output_tokens)
        ended = len(r.tokens) == r.max_output or (r.tokens and r.tokens[-1] == EOS)
        bad += (r.tokens != produced) or not ended
    return bad


def compare(cfg: dict, params, rows, *, control: bool = False) -> Dict[str, float]:
    """Readings of the sampled rows: ``widest_gap`` (of the served tokens;
    with ``control``, of the float8 reference's tokens in their place, and
    the served tokens' as ``program_widest_gap``), ``served_tokens`` and
    ``stream_faults``. The reference is the module the configuration names
    (``cfg["reference"]``), for the control too."""
    from harness import model

    ref_mod = model.reference(cfg)
    seqs = [list(r.prompt) + list(r.tokens[:-1]) for r in rows]
    reads = [list(range(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens)))
             for r in rows]
    ref = ref_mod.logits_at(cfg, params, seqs, reads)
    out = {"served_tokens": float(sum(len(r.tokens) for r in rows)),
           "stream_faults": float(stream_faults(rows))}
    gaps = [_gaps(lg, r.tokens) for lg, r in zip(ref, rows)]
    out["widest_gap"] = float(max(np.max(g) for g in gaps))
    if control:
        ctl = ref_mod.logits_at(cfg, params, seqs, reads, quant="fp8")
        cg = [_gaps(lg, np.asarray(jnp.argmax(c, axis=-1)))
              for lg, c in zip(ref, ctl)]
        out["program_widest_gap"] = out["widest_gap"]
        out["widest_gap"] = float(max(np.max(g) for g in cg))
    return out


def _gaps(logits, tokens) -> np.ndarray:
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return np.asarray(best - at)


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each compared number beside its limit; a run is correct when every
    number is at or under its limit."""
    return {k: {"value": readings[k], "limit": limits[k]} for k in limits}
