"""The traffic generator (``bench/traffic.py``) and its Markov text: one seed
gives one request list, every seed the same set of sizes, and the length,
rate and sharing parameters of each mix hold."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import markov  # noqa: E402
import traffic  # noqa: E402

VOCAB = 151_936
TEXT = markov.MarkovText(VOCAB, 0, span=1536)
RUN_SECONDS = 51


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def plan(name, seed, seconds=30.0):
    return traffic.make_plan(mix(name), TEXT, seed, seconds,
                             oneshot_max=129)


@pytest.mark.parametrize("name", ["chat-poisson", "chat-closed16",
                                  "docqa-prefix"])
def test_same_seed_same_list(name):
    a, b = plan(name, 2**31 + 7), plan(name, 2**31 + 7)
    assert a.window == b.window and a.warmup == b.warmup
    c = plan(name, 11)
    assert c.window != a.window


@pytest.mark.parametrize("name", ["chat-poisson", "chat-closed16"])
def test_chat_lengths(name):
    m = mix(name)
    g = traffic.Generator(m, TEXT, 3, RUN_SECONDS)
    reqs = [r for b in range(4) for r in g._block(b)]
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_tokens"] for r in reqs])
    pt, ot = m["prompt_tokens"], m["output_tokens"]
    assert p.min() >= pt["min"] and p.max() <= pt["max"]
    assert o.min() >= ot["min"] and o.max() <= ot["max"]
    assert (p + o).max() <= m["max_total_tokens"]
    # lognormal medians hold (the grid is the distribution's quantiles)
    assert abs(np.median(o) - ot["median"]) <= 0.03 * ot["median"]
    assert abs(np.median(p) - pt["median"]) <= 0.03 * pt["median"]
    # every category, evenly
    cats = {c: sum(r["category"] == c for r in reqs) for c in m["categories"]}
    assert set(cats) == set(markov.CATEGORIES)
    assert max(cats.values()) - min(cats.values()) <= 4


def test_every_seed_the_same_sizes():
    m = mix("chat-poisson")
    sizes = []
    for seed in (1, 2, 2**33 + 5):
        blk = traffic.Generator(m, TEXT, seed, RUN_SECONDS)._block(0)
        sizes.append(sorted(r["max_tokens"] for r in blk))
    assert sizes[0] == sizes[1] == sizes[2]


def test_open_loop_rate_and_window():
    m = mix("chat-poisson")
    p = plan("chat-poisson", 5, seconds=200.0)
    due = np.array([r["due"] for r in p.window])
    assert due[0] == 0.0 and due.max() < 200.0
    assert np.all(np.diff(due) > 0)
    assert len(p.window) == pytest.approx(200 * m["rate_per_s"], rel=0.05)


def test_closed_loop_has_enough_requests():
    p = plan("chat-closed16", 5, seconds=10.0)
    assert p.loop == "closed" and p.clients == 16
    assert len(p.window) == 16 * 10 * traffic.CLOSED_RATE_BOUND
    assert all("due" not in r for r in p.window)


def test_documents_zipf_and_prefix():
    m = mix("docqa-prefix")
    p = plan("docqa-prefix", 9, seconds=60.0)
    d = m["documents"]
    assert len(p.documents) == d["count"]
    assert all(len(doc) == d["tokens"] for doc in p.documents)
    counts = np.bincount([r["doc"] for r in p.window], minlength=d["count"])
    # Zipf(1) over 8: the first document about 2.7 times the fourth
    assert counts[0] > 2 * counts[3] > 0
    lo, hi = m["question_tokens"]["uniform"]
    for r in p.window[:50]:
        doc = p.documents[r["doc"]]
        assert r["prompt"][:len(doc)] == doc
        assert lo <= len(r["prompt"]) - len(doc) <= hi
    # the warm-up serves every document, then every document again
    w0, w1 = p.warmup
    assert sorted(r["doc"] for r in w0) == list(range(d["count"]))
    assert sorted(r["doc"] for r in w1[:d["count"]]) == list(range(d["count"]))


def test_warmup_covers_every_oneshot_length():
    p = plan("chat-poisson", 4)
    warm = {len(r["prompt"]) for wave in p.warmup for r in wave}
    g = traffic.Generator(mix("chat-poisson"), TEXT, 4, 30.0)
    short = {int(x) for x in g.prompt_grid if x <= 129}
    assert short and short <= warm
    assert {len(r["prompt"]) for r in p.window if len(r["prompt"]) <= 129} \
        <= warm
    # the second wave repeats first-wave prompts: prefix-cache hits
    assert all(r in p.warmup[0] for r in p.warmup[1])


def test_markov_text_follows_its_chain():
    t = markov.MarkovText(600, seed=3)
    x = t.sample("qa", 4, 50, (1, 2))
    r0, r1 = t.ranges["qa"]
    assert x.min() >= r0 and x.max() < r1
    for row in x:
        for a, b in zip(row[:-1], row[1:]):
            assert b - r0 in t.succ["qa"][a - r0]
    np.testing.assert_array_equal(x, t.sample("qa", 4, 50, (1, 2)))


def test_markov_tables_match_the_program_sampler():
    """The copy keeps the program's transition tables."""
    from repro.data.synthetic import SyntheticTasks
    prog = SyntheticTasks(600, seed=5)
    ours = markov.MarkovText(600, seed=5)
    for cat in markov.CATEGORIES:
        assert prog.ranges[cat] == ours.ranges[cat]
        np.testing.assert_array_equal(prog.next_tokens[cat], ours.succ[cat])
        np.testing.assert_allclose(np.cumsum(prog.next_probs[cat], axis=1),
                                   ours.cum[cat])
