"""The one traffic generator: a traffic mix's parameters -> a schedule.

A traffic mix is a JSON file under ``traffic/`` with two parts:

* ``churn``:  join/leave at ``rate_per_s``, kinds alternating join, leave,
  join, ... (a 1:1 ratio that holds the fleet's size);
* ``routes``: route queries at ``rate_per_s`` between two live nodes
  drawn uniformly.

Every seed gets the same amount of work: each part puts a fixed number of
arrivals, ``round(rate * seconds)``, into the warm-up and into the window,
at uniformly drawn (sorted) times, which is a Poisson process conditioned
on its count.  The seed picks the times and the nodes.  Route pairs are
drawn when a query is sent, from the nodes that are live by the events
acknowledged so far (``pick_pair``), from two uniforms fixed here.

Adapted from the program's generators, which stay as they are:
``poisson_churn`` (``src/repro/dynamics/scenarios.py``) and the uniform
mix of ``sample_pairs`` (``src/repro/routing/workload.py``).
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Sequence

import numpy as np


def rng_for(seed: int, *tag: int) -> np.random.Generator:
    """A generator from the run's seed (any whole number) and a tag."""
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), int(seed < 0), *tag]))


def _times(rng: np.random.Generator, rate: float, lo: float,
           hi: float) -> np.ndarray:
    """``round(rate * (hi - lo))`` sorted uniform arrival times in [lo, hi)."""
    return np.sort(rng.uniform(lo, hi, size=int(round(rate * (hi - lo)))))


def build_schedule(config: Dict, traffic: Dict, seed: int,
                   seconds: float) -> Dict:
    """The whole run's arrivals: ``warmup_s`` of the mix, then the window.

    Times are seconds from the start of the warm-up.  ``events`` holds
    ``[due, kind, node]`` in time order; ``routes`` holds ``[due, u1, u2]``.
    """
    n0, cap = int(config["n0"]), int(config["capacity"])
    warm = float(traffic["warmup_s"])
    end = warm + float(seconds)
    rng = rng_for(seed, 1)

    def arrivals(part):
        rate = float(traffic[part]["rate_per_s"])
        return np.concatenate([_times(rng, rate, 0.0, warm),
                               _times(rng, rate, warm, end)])

    fresh = iter(range(n0, cap))
    leavable = list(range(n0))
    events: List[list] = []
    for i, t in enumerate(arrivals("churn")):
        if i % 2 == 0:                 # a join takes the lowest fresh slot
            node = next(fresh, None)
            if node is None:
                raise ValueError("the mix needs more slots than the "
                                 f"capacity {cap} holds")
            kind = "join"
            bisect.insort(leavable, node)
        else:                          # a leave, a uniformly drawn live node
            node = leavable.pop(int(rng.integers(len(leavable))))
            kind = "leave"
        events.append([round(float(t), 6), kind, int(node)])

    times = arrivals("routes")
    u = rng.random((len(times), 2))
    routes = [[float(t), *map(float, row)] for t, row in zip(times, u)]
    return {"warmup_s": warm, "window_s": float(seconds), "n0": n0,
            "events": events, "routes": routes}


def pick_pair(live: Sequence[int], u1: float, u2: float) -> tuple:
    """Two distinct nodes of the sorted live list, from two uniforms."""
    n = len(live)
    src = live[min(int(u1 * n), n - 1)]
    j = min(int(u2 * (n - 1)), n - 2)
    i = bisect.bisect_left(live, src)
    return src, live[j + (j >= i)]
