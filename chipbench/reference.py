"""The plain reference: all-pairs shortest paths over the served overlay.

Straight Floyd-Warshall in ``jax.numpy`` over a dense matrix, one pivot at
a time, with no kernel, tiling or incremental state.  It imports nothing of
the program.  ``dtype="bfloat16"`` gives the control: the same reference
one precision below the float32 that the configuration states.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np


def dense(nodes: Sequence[int], edges: Sequence[Sequence[float]]
          ) -> Tuple[Dict[int, int], np.ndarray]:
    """Node id -> row, and the (n, n) float32 adjacency (inf off-edge)."""
    index = {int(u): i for i, u in enumerate(nodes)}
    adj = np.full((len(nodes), len(nodes)), np.inf, np.float32)
    np.fill_diagonal(adj, 0.0)
    for u, v, w in edges:
        i, j = index[int(u)], index[int(v)]
        adj[i, j] = adj[j, i] = min(adj[i, j], np.float32(w))
    return index, adj


def apsp(adj: np.ndarray, dtype: str = "float32") -> np.ndarray:
    """All-pairs shortest distances, computed in ``dtype`` on the default
    device and returned as float32.  The matrix is padded to a multiple of
    256 with isolated nodes, so nearby sizes share one compiled program."""
    import jax
    import jax.numpy as jnp

    n = adj.shape[0]
    m = -(-n // 256) * 256
    pad = np.full((m, m), np.inf, np.float32)
    np.fill_diagonal(pad, 0.0)
    pad[:n, :n] = adj

    @jax.jit
    def floyd_warshall(d):
        def pivot(k, d):
            return jnp.minimum(d, d[:, k][:, None] + d[k, :][None, :])
        return jax.lax.fori_loop(0, d.shape[0], pivot, d)

    out = floyd_warshall(jnp.asarray(pad, dtype=dtype))
    return np.asarray(out.astype(jnp.float32))[:n, :n]


def cc_diameter(dist: np.ndarray) -> float:
    """Largest finite distance inside the largest connected component."""
    finite = np.isfinite(dist)
    root = int(np.argmax(finite.sum(axis=1)))
    comp = np.flatnonzero(finite[root])
    return float(dist[np.ix_(comp, comp)].max())


def rel_gap(served: float, ref: float, lower_bound: bool) -> float:
    """Relative gap of a served distance from the reference; where the
    served matrix may be a lower bound, only an overestimate counts."""
    gap = (float(served) - float(ref)) / max(abs(float(ref)), 1e-9)
    return max(gap, 0.0) if lower_bound else abs(gap)


def path_sum(weights: Sequence[float], dtype: str) -> float:
    """A path's latency accumulated hop by hop in ``dtype`` (the control
    of a served distance against its own path)."""
    import jax.numpy as jnp

    kind = jnp.dtype(dtype).type         # rounds after every addition
    total = kind(0.0)
    for w in weights:
        total = kind(total + kind(w))
    return float(total)
