"""Small numeric helpers shared by the metric readers."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), None when empty."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def counter(run: dict, name: str) -> float:
    """A counter's change over the window."""
    return float(run["counters"][name])
