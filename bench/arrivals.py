"""The one arrival generator: every open-loop traffic mix is a data file of
its parameters (``traffic/<name>.json``), read here.

A mix is a Poisson stream of probe classes in fixed shares::

  {"kind": "open",
   "rate_per_s": 200,
   "classes": {"prefill": 1.0, "decode": 5.28},   # relative weights
   "source": "..."}                                # where the mix comes from

The number of arrivals in a window is fixed by the parameters, not drawn:
the stream holds ``round(rate * seconds)`` arrivals, placed as sorted
uniform times (a Poisson process given its count), with each class's
share exact and the order shuffled. So every seed offers the same work in
another order, and runs with different seeds differ by the order alone.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Arrivals:
    due_s: np.ndarray  # (n,) float64, sorted, relative to the window start
    kind: List[str]  # (n,) class name per arrival

    def __len__(self) -> int:
        return len(self.kind)


def _exact_shares(rng, n: int, classes: Dict[str, float]) -> List[str]:
    names = sorted(classes)
    total = sum(classes[c] for c in names)
    counts = [int(round(n * classes[c] / total)) for c in names]
    counts[-1] = n - sum(counts[:-1])
    kinds = [c for c, k in zip(names, counts) for _ in range(k)]
    rng.shuffle(kinds)
    return kinds


def open_loop(params: Dict, seconds: float, rng) -> Arrivals:
    """Arrival times and classes of one window of ``seconds``."""
    if params.get("kind") != "open":
        raise ValueError(f"not an open-loop mix: {params.get('kind')!r}")
    n = int(round(float(params["rate_per_s"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    return Arrivals(due_s=due, kind=_exact_shares(rng, n, params["classes"]))
