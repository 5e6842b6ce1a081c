"""The one traffic generator: a mix file of parameters in, a request plan out.

A mix is a JSON file ``bench/traffic/<name>.json``.  Its keys:

``loop``            ``"open"`` (requests fall due on a schedule, whatever the
                    server does) or ``"closed"`` (``clients`` callers, each
                    sending its next request when its last one has ended).
``rate_per_s``      open loop: mean arrivals per second (Poisson gaps).
``clients``         closed loop: concurrent callers.
``categories``      Markov text categories the prompts are drawn from.
``prompt_tokens``   ``{"median", "sigma", "min", "max"}``: lognormal lengths,
                    clipped.
``output_tokens``   the same for ``max_tokens``, or ``{"uniform": [lo, hi]}``.
``documents``       optional shared prefixes: ``{"count", "tokens",
                    "categories", "zipf_s"}``; every prompt is then one
                    document (drawn Zipf(s)) followed by a question of
                    ``question_tokens`` (``{"uniform": [lo, hi]}``) drawn from
                    ``question_category``.
``strata``          closed loop: size of the fixed set of sizes (see below).
                    Open loop it is ``round(rate_per_s * seconds)``, the
                    requests due in one window.
``max_total_tokens`` prompt + output never exceed this; a longer prompt is
                    cut to fit (rare: only the clipped tails meet it).

Every seed gets the same set of sizes and arrival gaps, in another order:
lengths and gaps are the quantiles ``(i + 0.5) / strata`` of their
distributions, and the seed permutes each block of ``strata`` requests and
draws the text.  So two seeds do the same amount of work (open loop, a
window is exactly one block), and the set of prompt shapes, which sets what
the warm-up must compile, is fixed.

The warm-up set is part of the plan: one request for every prompt length
the engine admits in one shot (``<= oneshot_max``, one compiled program
each), a few of the longest prompts (the chunked path), and with documents
each document twice (the second time a prefix-cache hit).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

from markov import MarkovText

WARMUP_OUTPUT_TOKENS = 16
WARMUP_LONG_PROMPTS = 8
# requests per second one closed-loop caller can never exceed: a request is
# at least a prefill and one superstep, far over half a second at these
# widths.  A caller that runs out anyway fails the run (client.py).
CLOSED_RATE_BOUND = 4


@dataclass
class Plan:
    loop: str
    clients: int
    warmup: List[List[dict]]        # waves, served in turn
    window: List[dict]
    documents: List[list] = field(default_factory=list)


def load_mix(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles of ``spec``'s distribution."""
    q = quantiles(n)
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return (lo + np.floor(q * (hi - lo + 1))).astype(np.int64)
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    raw = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)


def gap_grid(rate: float, n: int) -> np.ndarray:
    """Exponential inter-arrival gaps at the quantiles: a Poisson process's
    gaps, the same set for every seed."""
    return -np.log1p(-quantiles(n)) / rate


def zipf_counts(k: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests go to each of ``k`` documents under
    Zipf(s), by largest remainders so that they sum to ``n``."""
    p = 1.0 / np.arange(1, k + 1) ** s
    want = p / p.sum() * n
    counts = np.floor(want).astype(np.int64)
    rest = np.argsort(-(want - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return counts


def strata(mix: dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    return int(mix["strata"])


class Generator:
    def __init__(self, mix: dict, text: MarkovText, seed: int,
                 seconds: float):
        self.mix = mix
        self.seed = int(seed)
        self.strata = strata(mix, seconds)
        self.text = text
        self.prompt_grid = (None if "documents" in mix
                            else length_grid(mix["prompt_tokens"],
                                             self.strata))
        self.out_grid = length_grid(mix["output_tokens"], self.strata)
        self.cap = int(mix["max_total_tokens"])
        self.docs: List[np.ndarray] = []
        if "documents" in mix:
            d = mix["documents"]
            self.q_grid = length_grid(mix["question_tokens"], self.strata)
            for i in range(d["count"]):
                cat = d["categories"][i % len(d["categories"])]
                self.docs.append(self.text.sample(
                    cat, 1, d["tokens"], (self.seed, 1, i))[0])
            self.doc_pick = np.repeat(np.arange(d["count"]), zipf_counts(
                d["count"], d["zipf_s"], self.strata))

    def _rng(self, *key) -> np.random.Generator:
        return np.random.default_rng([self.seed, *key])

    def _block(self, b: int) -> List[dict]:
        """Block ``b``: ``strata`` requests, a permutation of the fixed
        sizes, with fresh text."""
        n = self.strata
        rng = self._rng(2, b)
        outs = rng.permutation(self.out_grid)
        cats = list(self.mix["categories"])
        reqs = []
        if self.docs:
            picks = rng.permutation(self.doc_pick)
            qlens = rng.permutation(self.q_grid)
            qcat = self.mix["question_category"]
            qs = self.text.sample(qcat, n, int(qlens.max()), (self.seed, 3, b))
            for i in range(n):
                prompt = np.concatenate([self.docs[picks[i]],
                                         qs[i, :qlens[i]]])
                reqs.append(self._req(prompt, outs[i], qcat,
                                      doc=int(picks[i])))
            return reqs
        plens = rng.permutation(self.prompt_grid)
        cat_of = rng.permutation(np.arange(n) % len(cats))
        for ci, cat in enumerate(cats):
            idx = np.flatnonzero(cat_of == ci)
            if not len(idx):
                continue
            text = self.text.sample(cat, len(idx), int(plens[idx].max()),
                                    (self.seed, 4, b, ci))
            for j, i in enumerate(idx):
                reqs.append((i, self._req(text[j, :plens[i]], outs[i], cat)))
        return [r for _, r in sorted(reqs, key=lambda t: t[0])]

    def _req(self, prompt, max_tokens, cat, doc=None) -> dict:
        max_tokens = int(max_tokens)
        prompt = np.asarray(prompt, np.int64)[-(self.cap - max_tokens):]
        r = {"prompt": prompt.tolist(), "max_tokens": max_tokens,
             "category": cat}
        if doc is not None:
            r["doc"] = doc
        return r

    def window(self, seconds: float) -> List[dict]:
        """The requests of a window of ``seconds``: open loop, the first
        ``round(rate * seconds)`` requests due before its end, with their due
        times (one whole block: every seed sends the same sizes); closed
        loop, enough for the callers never to run out."""
        mix = self.mix
        if mix["loop"] == "open":
            rate = float(mix["rate_per_s"])
            gaps = gap_grid(rate, self.strata)
            count = int(round(rate * seconds))
            out, t, b = [], 0.0, 0
            while True:
                perm = self._rng(5, b).permutation(gaps)
                for r, g in zip(self._block(b), perm):
                    if t >= seconds or len(out) == count:
                        return out
                    r["due"] = t
                    out.append(r)
                    t += float(g)
                b += 1
        # closed loop: more than a caller can send (see CLOSED_RATE_BOUND)
        need = int(math.ceil(mix["clients"] * seconds * CLOSED_RATE_BOUND))
        out, b = [], 0
        while len(out) < need:
            out.extend(self._block(b))
            b += 1
        return out[:need]

    def warmup(self, oneshot_max: int) -> List[List[dict]]:
        """Every shape the window can use, with short outputs, in two waves
        served one after the other.  The second wave hits the prefix cache
        of the first: exact repeats (a fully cached prompt, a copy-on-write
        page) and, with documents, each document with a new question."""
        cats = self.mix["categories"]
        rng = self._rng(6)
        first = []
        if self.docs:
            qcat = self.mix["question_category"]
            second = []
            for rep, wave in enumerate((first, second)):
                for i, doc in enumerate(self.docs):
                    q = self.text.sample(qcat, 1, int(self.q_grid.max()),
                                         (self.seed, 7, rep, i))[0]
                    n = int(self.q_grid[(i * 7 + rep * 3) % self.strata])
                    wave.append(self._req(np.concatenate([doc, q[:n]]),
                                          WARMUP_OUTPUT_TOKENS, qcat, doc=i))
        else:
            short = sorted({int(x) for x in self.prompt_grid
                            if x <= oneshot_max})
            longest = np.sort(self.prompt_grid)[-WARMUP_LONG_PROMPTS:]
            for i, n in enumerate(short + [int(x) for x in longest]):
                cat = cats[int(rng.integers(len(cats)))]
                p = self.text.sample(cat, 1, n, (self.seed, 8, i))[0]
                first.append(self._req(p, WARMUP_OUTPUT_TOKENS, cat))
            second = []
        second += [dict(first[0]), dict(first[-1])]
        return [first, second]


def make_plan(mix: dict, text: MarkovText, seed: int, seconds: float,
              oneshot_max: int) -> Plan:
    g = Generator(mix, text, seed, seconds)
    return Plan(loop=mix["loop"], clients=int(mix.get("clients", 0)),
                warmup=g.warmup(oneshot_max), window=g.window(seconds),
                documents=[d.tolist() for d in g.docs])
