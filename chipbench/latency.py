"""The benchmark's own copy of the configurations' latency models.

Copied from ``src/repro/core/topology.py`` (``bitnode_latency``) so that
the yardstick does not move when the program does.  Given the same seed it
draws the same numbers in the same order as the program's model: every
served edge weight must equal an entry of :func:`latency_matrix` exactly.
"""
from __future__ import annotations

import numpy as np

# Bitnodes: 7 regions (NA, SA, EU, AS, AF, CN, OC), population shares and an
# inter-region one-way latency table in ms
BITNODE_WEIGHTS = np.array([0.32, 0.04, 0.36, 0.12, 0.02, 0.06, 0.08])
BITNODE_MS = np.array([
    [20.0, 75., 45.0, 90., 120., 95., 80.],
    [75.0, 25., 95.0, 160., 150., 170., 140.],
    [45.0, 95., 12.0, 80., 70., 110., 130.],
    [90.0, 160., 80.0, 30., 130., 50., 65.],
    [120.0, 150., 70.0, 130., 40., 150., 160.],
    [95.0, 170., 110., 50., 150., 18., 90.],
    [80.0, 140., 130., 65., 160., 90., 15.],
], dtype=np.float64)


def _symmetrize(m: np.ndarray) -> np.ndarray:
    out = np.triu(m, 1)
    out = out + out.T
    np.fill_diagonal(out, 0.0)
    return out.astype(np.float32)


def bitnode(rng: np.random.Generator, n: int) -> np.ndarray:
    """Region by population share, the region pair's latency, plus a
    Gamma(2, 2.5) last-mile term per pair."""
    region_of = rng.choice(len(BITNODE_WEIGHTS), size=n, p=BITNODE_WEIGHTS)
    base = BITNODE_MS[np.ix_(region_of, region_of)]
    jitter = rng.gamma(2.0, 2.5, size=(n, n))
    return _symmetrize(base + jitter)


MODELS = {"bitnode": bitnode}


def latency_matrix(model: str, n: int, seed: int) -> np.ndarray:
    """The (n, n) float32 latency matrix of ``model`` drawn from ``seed``."""
    if model not in MODELS:
        raise ValueError(f"unknown latency model {model!r}; "
                         f"options {sorted(MODELS)}")
    return MODELS[model](np.random.default_rng(seed), n)
