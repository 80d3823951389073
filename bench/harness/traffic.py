"""relQuery traffic from a seed: one general generator driven by a mix file.

A mix file (``bench/traffic/<name>.json``) holds only parameters. The
generator turns it and ``--seed`` into a list of ``Query`` records (prompt
token ids, output limit, due time) and nothing else reaches the program.

The tables, templates and tokenizer are a frozen copy of the repository's
relQuery trace generator (paper section 5.1: Table 4 prompt and output
statistics, shared item values, the five task templates of Table 5), kept
here so that a change to the program cannot move the yardstick.

Every seed of one mix gets the same work. A mix fixes a deck of relQueries
(rows, template) and a set of inter-arrival gaps, neither of which depends on
the window's length: rows are stratified over the mix's range and paired
with the templates so that every template gets the same mean row count, and
gaps are stratified quantiles of the exponential distribution at the mix's
rate, so a deck is a Poisson process with its sampling noise taken out. An
open mix repeats its deck, in one fixed low-discrepancy order (a
golden-ratio sequence, so that large and small relQueries and short and long
gaps alternate), from ``preroll_s`` before the window opens, so that the
window opens on the queue that the rate sustains. A backlog is one deck, all
due when the window opens. With a handful of relQueries in a window, a
shuffled order would change the work from seed to seed. The table's shape
(the word count of each catalog item and review, which item each row shows,
and which rows each relQuery takes) comes from the dataset alone, so every
seed sends prompts of the same lengths with the same shared prefixes; the
seed draws the words, and with them every token.
"""
from __future__ import annotations

import hashlib
import json
import math
import random
import zlib
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

# (avg prompt tokens, avg output tokens) per paper Table 4
DATASET_STATS: Dict[str, Tuple[int, int]] = {
    "amazon": (234, 18),
    "rotten": (215, 21),
    "beer": (174, 19),
    "pdmx": (158, 23),
}

# output-length limits per query type (paper section 5.1)
OUTPUT_LIMITS = {"filter": 5, "classify": 10, "rating": 5, "summarize": 50,
                 "open": 100}

_WORDS = [f"w{i:03d}" for i in range(800)]
ITEMS_PER_CATALOG = 64
TABLE_ROWS = 2000


def template_text(qtype: str, item: str, review: str) -> str:
    """The five task templates (paper Table 5), rendered for one row."""
    return {
        "filter": "Decide whether this item is suitable for children based on "
                  f"the description {item} . Answer yes or no only .",
        "classify": f"Categorize the sentiment of the review {review} as "
                    "Negative , Positive , or Neutral .",
        "rating": f"Predict the user's rating from 1 to 5 based on the item "
                  f"{item} and the comment {review} . Output only the digit "
                  "and nothing else .",
        "summarize": f"Summarize the user's review {review} on the item "
                     f"{item} within 20 words .",
        "open": "Who are the most likely audiences for this item given its "
                f"description {item} and a sample review {review} ? "
                "Explain briefly .",
    }[qtype]


class HashTokenizer:
    """Whitespace words to stable ids in [2, vocab_size): identical text gives
    identical ids, so shared item values give shared token prefixes."""

    def __init__(self, vocab_size: int, bos: int = 1, eos: int = 0):
        self.vocab_size = vocab_size
        self.bos = bos
        self.eos = eos

    def _tok(self, word: str) -> int:
        h = int.from_bytes(hashlib.blake2s(word.encode(), digest_size=4)
                           .digest(), "little")
        return 2 + h % (self.vocab_size - 2)

    def encode(self, text: str) -> List[int]:
        return [self.bos] + [self._tok(w) for w in text.split()]


def _sentence(rng: random.Random, n: int) -> str:
    return " ".join(rng.choice(_WORDS) for _ in range(n))


def shape_rng(dataset: str, part: str) -> random.Random:
    """A generator of the table's shape: the dataset's alone."""
    return random.Random(f"{dataset}/{part}")


def make_table(dataset: str, seed: int) -> List[Dict[str, str]]:
    """Rows that reference a small catalog of shared item descriptions (value
    overlap) and carry unique review text, sized to Table 4's prompt means.
    The word counts and each row's item are the same for every seed; the
    seed draws the words."""
    avg_in, _ = DATASET_STATS[dataset]
    shape = shape_rng(dataset, "table")
    rng = random.Random(seed ^ zlib.crc32(dataset.encode()))
    item_words = max(8, int(avg_in * 0.42))
    review_words = max(8, avg_in - item_words - 25)
    item_lens = [max(4, int(shape.gauss(item_words, item_words * 0.25)))
                 for _ in range(ITEMS_PER_CATALOG)]
    row_shapes = [(shape.randrange(ITEMS_PER_CATALOG),
                   max(4, int(shape.gauss(review_words, review_words * 0.3))))
                  for _ in range(TABLE_ROWS)]
    catalog = [_sentence(rng, n) for n in item_lens]
    return [{"item": catalog[item], "review": _sentence(rng, n)}
            for item, n in row_shapes]


@dataclass
class Query:
    """One relQuery as the client sends it."""
    qid: str
    qtype: str
    due: float                      # seconds after the window opens
    max_output_tokens: int
    prompts: List[List[int]]


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    need = {"open": ("rate_rps", "deck", "preroll_s", "drain_s"),
            "backlog": ("relqueries",)}
    if mix["arrival"] not in need:
        raise ValueError(f"{path}: arrival must be 'open' or 'backlog'")
    missing = [k for k in need[mix["arrival"]] if k not in mix]
    if missing:
        raise ValueError(f"{path}: {mix['arrival']} mix lacks {missing}")
    unknown = set(mix["templates"]) - set(OUTPUT_LIMITS)
    if unknown:
        raise ValueError(f"{path}: unknown templates {sorted(unknown)}")
    if mix["dataset"] not in DATASET_STATS:
        raise ValueError(f"{path}: unknown dataset {mix['dataset']!r}")
    return mix


def deck_size(mix: dict) -> int:
    return int(mix["relqueries"] if mix["arrival"] == "backlog" else mix["deck"])


def deck(mix: dict, n: int) -> List[Tuple[int, str]]:
    """The fixed list of (rows, template) of a deck, in the order it is
    sent, the same for every seed. Rows are stratified over the mix's range
    and paired with the templates in a snake order (0..k-1, then k-1..0,
    ...), so that over each 2k strata every template gets the same mean row
    count. The templates are sent in turn, each one's row counts ascending
    for even templates and descending for odd ones, so that large and small
    relQueries alternate."""
    lo, hi = mix["rows"]
    tpls = list(mix["templates"])
    k = len(tpls)
    rows = [min(hi, lo + int((hi - lo + 1) * (i + 0.5) / n)) for i in range(n)]
    own: Dict[int, List[int]] = {t: [] for t in range(k)}
    for i, r in enumerate(rows):
        own[i % k if (i // k) % 2 == 0 else k - 1 - i % k].append(r)
    for t in own:
        own[t].sort(reverse=t % 2 == 1)
    order = sorted(((c, t) for t in own for c in range(len(own[t]))))
    return [(own[t][c], tpls[t]) for c, t in order]


def gaps(mix: dict, n: int) -> List[float]:
    """n stratified exponential quantiles with mean 1 / rate; all zero for a
    backlog."""
    if mix["arrival"] == "backlog":
        return [0.0] * n
    q = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / float(mix["rate_rps"]) / sum(q)
    return [x * scale for x in q]


def spread_order(n: int, alpha: float) -> List[int]:
    """A fixed order of 0..n-1 in which neighbours in the order sit far
    apart in value: i sorted by the fractional part of i * alpha."""
    return sorted(range(n), key=lambda i: (i * alpha) % 1.0)


def generate(mix: dict, seed: int, until: float,
             vocab_size: int) -> List[Query]:
    """Every relQuery due before ``until`` seconds after the window opens,
    in due order. An open mix repeats its deck: the deck's first relQuery is
    due when the window opens, each gap follows the relQuery of its
    position, and the end of the previous cycle is due in the
    ``preroll_s`` before the window opens (negative due times). A backlog
    is one deck due at 0. Pure function of its inputs."""
    rng = random.Random(seed)
    n = deck_size(mix)
    work = deck(mix, n)
    gap = gaps(mix, n)
    gap = [gap[i] for i in spread_order(n, 0.6180339887498949)]
    if mix["arrival"] == "backlog":
        slots = [(0.0, w) for w in work]
    else:
        slots, t, j = [], 0.0, -1
        while True:                       # the previous cycle, backwards
            t -= gap[j % n]
            if t < -float(mix["preroll_s"]):
                break
            slots.insert(0, (t, work[j % n]))
            j -= 1
        t, j = 0.0, 0
        while t < until:
            slots.append((t, work[j % n]))
            t += gap[j % n]
            j += 1
    table = make_table(mix["dataset"], rng.randrange(2 ** 32))
    place = shape_rng(mix["dataset"], "offsets")
    tok = HashTokenizer(vocab_size=vocab_size - 2)
    out = []
    for i, (due, (rows, qtype)) in enumerate(slots):
        offset = place.randrange(0, len(table) - rows)
        prompts = [tok.encode(template_text(qtype, row["item"], row["review"]))
                   for row in table[offset:offset + rows]]
        out.append(Query(f"q{i}", qtype, due, OUTPUT_LIMITS[qtype], prompts))
    return out


def summary(queries: Sequence[Query]) -> dict:
    """Counts a reader can check a mix against."""
    lens = [len(p) for q in queries for p in q.prompts]
    return {"relqueries": len(queries),
            "rows": len(lens),
            "prompt_tokens_mean": sum(lens) / max(1, len(lens)),
            "prompt_tokens_max": max(lens, default=0),
            "output_tokens_max": max((q.max_output_tokens for q in queries),
                                     default=0)}
