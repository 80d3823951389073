"""Traffic is a pure function of the seed, and every seed gets the same work."""
import json
import math
from collections import Counter
from pathlib import Path

import pytest

from harness import traffic

BENCH = Path(__file__).resolve().parents[1]
MIX = traffic.load_mix(BENCH / "traffic" / "relq-mix.knee80-qwen3-1.7b.json")
SHORT = traffic.load_mix(BENCH / "traffic" / "relq-short.backlog.json")


def test_same_seed_same_traffic():
    a = traffic.generate(MIX, 2 ** 40 + 3, 51, 151936)
    b = traffic.generate(MIX, 2 ** 40 + 3, 51, 151936)
    assert [(q.qid, q.qtype, q.due, q.prompts) for q in a] == \
        [(q.qid, q.qtype, q.due, q.prompts) for q in b]


def test_seeds_share_the_deck_in_one_order():
    a = traffic.generate(MIX, 1, 51, 151936)
    b = traffic.generate(MIX, 2, 51, 151936)
    work = lambda qs: [(len(q.prompts), q.qtype) for q in qs]
    dues = lambda qs: [round(q.due, 9) for q in qs]
    assert work(a) == work(b) and dues(a) == dues(b)
    assert [q.prompts for q in a] != [q.prompts for q in b]


@pytest.mark.parametrize("mix", [MIX, SHORT], ids=["open", "backlog"])
def test_seeds_send_prompts_of_the_same_lengths(mix):
    """The seed draws the words alone: every seed sends the same prompt
    lengths, row for row, and shares prefixes among the same rows."""
    a, b = (traffic.generate(mix, s, 141, 151936) for s in (11, 2 ** 31 + 9))
    lens = lambda qs: [[len(p) for p in q.prompts] for q in qs]
    assert lens(a) == lens(b)

    def shared(qs):
        first = {}
        return [first.setdefault(tuple(p[:24]), (i, j))
                for i, q in enumerate(qs) for j, p in enumerate(q.prompts)]
    assert shared(a) == shared(b)
    assert [q.prompts for q in a] != [q.prompts for q in b]


def test_spread_order_alternates_sizes():
    qs = traffic.generate(MIX, 1, 100, 151936)
    big = [len(q.prompts) > 50 for q in qs]
    run = longest = 1
    for prev, cur in zip(big, big[1:]):
        run = run + 1 if cur == prev else 1
        longest = max(longest, run)
    assert longest <= 3


@pytest.mark.parametrize("seconds", [10, 51])
def test_open_arrivals_fit_the_window_at_the_rate(seconds):
    """The deck does not depend on the window: a shorter one sees a prefix
    of the same arrivals. The window opens on the deck's first relQuery,
    the pre-roll holds the end of the previous cycle, and a whole cycle
    lasts its relQueries over the rate."""
    qs = traffic.generate(MIX, 7, seconds, 151936)
    longer = traffic.generate(MIX, 7, 400, 151936)
    key = lambda q: (len(q.prompts), q.qtype, round(q.due, 9))
    assert [key(q) for q in qs] == [key(q) for q in longer[:len(qs)]]
    dues = [q.due for q in longer]
    assert dues == sorted(dues)
    assert -MIX["preroll_s"] <= dues[0] < 0 and dues[-1] < 400
    window = [q for q in qs if q.due >= 0]
    assert window[0].due == 0.0 and all(q.due < seconds for q in qs)
    n = MIX["deck"]
    cycle = [q for q in longer if q.due >= 0]
    assert cycle[n].due == pytest.approx(n / MIX["rate_rps"])
    pre = [key(q)[:2] for q in longer if q.due < 0]
    assert pre == [key(q)[:2] for q in cycle[n - len(pre):n]]


def test_backlog_is_due_at_once_and_short():
    qs = traffic.generate(SHORT, 5, 51, 151936)
    assert len(qs) == SHORT["relqueries"]
    assert all(q.due == 0.0 for q in qs)
    assert max(q.max_output_tokens for q in qs) <= 10


def test_shared_item_values_give_shared_prefixes():
    qs = traffic.generate(MIX, 3, 400, 151936)
    filt = [p for q in qs if q.qtype == "filter" for p in q.prompts]
    firsts = Counter(tuple(p) for p in filt)
    assert max(firsts.values()) > 1      # same item, same filter prompt


def test_mix_files_are_valid():
    for path in (BENCH / "traffic").glob("*.json"):
        mix = traffic.load_mix(path)
        assert mix["why"] and len(mix["why"]) <= 200
