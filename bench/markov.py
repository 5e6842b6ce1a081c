"""Spec-Bench-like synthetic token text: one sparse Markov chain per category.

A copy of the program's task sampler (``src/repro/data/synthetic.py``): the
same six categories and transition tables, drawn in the same order from
``default_rng(seed)``.  Three things differ:

* paths are seeded from a tuple of integers (``default_rng([...])``), never
  through Python's salted ``hash()``, so every process draws the same text;
* a batch of paths is drawn in one vectorised pass (one uniform per token,
  inverted through the cumulative transition probabilities), which keeps a
  run's set-up short;
* ``span`` can narrow each category's range to its first ``span`` tokens
  (the program's own is the whole vocabulary split six ways), so that the
  benchmark's weights can hold every transition (``model_ref``).

Token 0 is padding and 1 is EOS; neither is ever drawn.
"""
from __future__ import annotations

import numpy as np

CATEGORIES = ("mt_bench", "translation", "summarization", "qa", "math", "rag")


class MarkovText:
    def __init__(self, vocab_size: int, seed: int, branching: int = 4,
                 span: int = 0):
        rng = np.random.default_rng(seed)
        self.vocab = vocab_size
        self.branching = branching
        lo = 2
        full = (vocab_size - lo) // len(CATEGORIES)
        if span > full:
            raise ValueError(f"span {span} over the {full} tokens a "
                             f"category has")
        span = span or full
        self.ranges = {}
        self.succ = {}
        self.cum = {}
        for ci, cat in enumerate(CATEGORIES):
            r0 = lo + ci * full
            self.ranges[cat] = (r0, r0 + span)
            self.succ[cat] = rng.integers(0, span, size=(span, branching))
            probs = rng.dirichlet(np.ones(branching) * 0.5, size=span)
            self.cum[cat] = np.cumsum(probs, axis=1)

    def sample(self, cat: str, n: int, length: int, key) -> np.ndarray:
        """``n`` paths of ``length`` tokens of category ``cat``, drawn from
        ``default_rng(key)``; ``key`` is a tuple of non-negative ints."""
        r0, r1 = self.ranges[cat]
        rng = np.random.default_rng([int(k) for k in key])
        succ, cum = self.succ[cat], self.cum[cat]
        out = np.empty((n, length), np.int32)
        cur = rng.integers(0, r1 - r0, size=n)
        u = rng.random((length, n))
        for t in range(length):
            out[:, t] = r0 + cur
            choice = (u[t][:, None] > cum[cur]).sum(axis=1)
            cur = succ[cur, np.minimum(choice, self.branching - 1)]
        return out

    def table(self):
        """Every transition: (tokens (n,), successors (n, branching),
        probabilities (n, branching)), as token ids."""
        toks, succ, prob = [], [], []
        for cat in CATEGORIES:
            r0, r1 = self.ranges[cat]
            cum = self.cum[cat]
            toks.append(np.arange(r0, r1))
            succ.append(r0 + self.succ[cat])
            prob.append(np.diff(cum, axis=1, prepend=0.0))
        return (np.concatenate(toks).astype(np.int32),
                np.concatenate(succ).astype(np.int32),
                np.concatenate(prob).astype(np.float32))
