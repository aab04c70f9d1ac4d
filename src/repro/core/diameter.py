"""Diameter / APSP primitives.

Two implementations, cross-validated in tests:

* ``apsp`` / ``diameter``: jit-able JAX min-plus matrix-squaring APSP
  (O(N^3 log N)).  Used inside the Q-learning reward (small N, on-device) and
  on TPU, where the inner min-plus step is the Pallas kernel in
  ``repro.kernels.minplus`` (CPU falls back to the jnp oracle automatically).
* ``diameter_scipy``: host-side Dijkstra oracle (scipy csgraph) for large-N
  benchmark sweeps — the paper itself uses NetworkX; scipy is ~100x faster
  and agrees exactly (see tests/test_diameter.py).

Disconnected graphs follow the paper (§IV-C): "the diameter of the largest
connected component is adopted".
"""
from __future__ import annotations

import functools
from typing import Iterable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

# finite "infinity": avoids inf-inf NaN in min-plus.  A host scalar, so
# importing this module creates no device array (and takes no chip).
INF = np.float32(1e9)

__all__ = [
    "INF",
    "is_edge",
    "neighbour_lists",
    "adjacency_from_edges",
    "ring_edges",
    "adjacency_from_rings",
    "minplus",
    "apsp",
    "relax_edge_update",
    "largest_cc_diameter",
    "diameter",
    "diameter_of_rings",
    "diameter_scipy",
]


# ---------------------------------------------------------------------------
# graph assembly
# ---------------------------------------------------------------------------

def is_edge(adj):
    """Boolean mask of actual edges in a weighted adjacency matrix.

    An entry is an edge iff it is strictly positive (excludes the 0 diagonal)
    and below the INF sentinel.  The ``INF / 2`` guard absorbs sentinel
    round-off from device round-trips; works on numpy and jax arrays alike.
    """
    return (adj > 0) & (adj < float(INF) / 2)


def neighbour_lists(adj: np.ndarray) -> list:
    """Per-node neighbour index lists, from one vectorized ``is_edge`` pass.

    Event loops that look up neighbours per event should call this once per
    overlay instead of re-scanning adjacency rows."""
    mask = np.asarray(is_edge(adj))
    return [np.flatnonzero(mask[u]) for u in range(mask.shape[0])]


def ring_edges(perm: np.ndarray) -> np.ndarray:
    """Edges of the ring perm[0] -> perm[1] -> ... -> perm[-1] -> perm[0]."""
    perm = np.asarray(perm)
    return np.stack([perm, np.roll(perm, -1)], axis=1)


def adjacency_from_edges(w: np.ndarray, edges: Iterable[Sequence[int]]) -> np.ndarray:
    """Weighted adjacency with INF on non-edges, 0 diagonal (undirected).

    Vectorized scatter: ``np.minimum.at`` handles duplicate edges exactly like
    the per-edge ``min`` loop it replaced (parallel-edge weight = min).
    """
    n = w.shape[0]
    d = np.full((n, n), float(INF), dtype=np.float32)
    np.fill_diagonal(d, 0.0)
    e = np.asarray(edges if isinstance(edges, np.ndarray) else list(edges),
                   dtype=np.intp).reshape(-1, 2)
    if e.size:
        if e.min() < 0 or e.max() >= n:
            raise ValueError(
                f"edge endpoints must lie in [0, {n}); got range "
                f"[{e.min()}, {e.max()}]")
        u, v = e[:, 0], e[:, 1]
        np.minimum.at(d, (u, v), w[u, v].astype(np.float32))
        np.minimum.at(d, (v, u), w[v, u].astype(np.float32))
    return d


def adjacency_from_rings(w: np.ndarray, perms: Sequence[np.ndarray]) -> np.ndarray:
    """Union of K rings as a weighted adjacency matrix.

    Every ring must be a permutation of ``range(n)`` — a shorter / repeated
    ring would silently produce an overlay over the wrong node set.
    """
    n = w.shape[0]
    ident = np.arange(n)
    for i, p in enumerate(perms):
        p = np.asarray(p)
        if p.shape != (n,) or not np.array_equal(np.sort(p), ident):
            raise ValueError(
                f"ring {i} is not a permutation of range({n}): "
                f"shape {p.shape}, unique {np.unique(p).size}")
    edges = np.concatenate([ring_edges(p) for p in perms], axis=0)
    return adjacency_from_edges(w, edges)


# ---------------------------------------------------------------------------
# JAX min-plus APSP
# ---------------------------------------------------------------------------

def _minplus_jnp(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(A ⊗ B)[i,j] = min_k A[i,k] + B[k,j] — the tropical-semiring matmul."""
    return jnp.min(a[:, :, None] + b[None, :, :], axis=1)


def minplus(a: jnp.ndarray, b: jnp.ndarray, *, use_kernel: bool = False) -> jnp.ndarray:
    """Min-plus product; Pallas tiled kernel on TPU when requested."""
    if use_kernel:
        from repro.kernels.minplus import ops as minplus_ops

        return minplus_ops.minplus(a, b)
    return _minplus_jnp(a, b)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def apsp(adj: jnp.ndarray, *, use_kernel: bool = False) -> jnp.ndarray:
    """All-pairs shortest paths by repeated min-plus squaring.

    ``adj`` is a weighted adjacency matrix (0 diag, INF non-edges).  After
    ceil(log2(N-1)) squarings D contains shortest-path distances.
    """
    n = adj.shape[0]
    n_iters = max(1, int(np.ceil(np.log2(max(n - 1, 2)))))

    def body(_, d):
        return minplus(d, d, use_kernel=use_kernel)

    return jax.lax.fori_loop(0, n_iters, body, adj)


def relax_edge_update(dist: jnp.ndarray, u: jnp.ndarray, v: jnp.ndarray,
                      wuv: jnp.ndarray) -> jnp.ndarray:
    """Exact O(N^2) repair of an APSP matrix after inserting edge (u, v).

    With positive weights a new shortest path crosses the inserted edge at
    most once, so ``D' = min(D, D[:,u] + w + D[v,:], D[:,v] + w + D[u,:])``
    is exact.  Shared by the churn engine (``dynamics.incremental``) and the
    DQN rollout engine (``core.rollout``), which uses it as the in-scan
    carry update replacing a full O(N^3) APSP per reward.
    """
    du = dist[:, u]                       # distances into u
    dv = dist[:, v]
    via = jnp.minimum(du[:, None] + wuv + dist[v, :][None, :],
                      dv[:, None] + wuv + dist[u, :][None, :])
    return jnp.minimum(dist, via)


def largest_cc_diameter(d: jnp.ndarray) -> jnp.ndarray:
    """Diameter of the largest connected component given APSP distances
    (paper §IV-C).  Shared by the unbatched path and ``core.batcheval``.

    Accepts reduced-precision distance matrices (the bf16 / int16-quantized
    eval paths in ``batcheval``): the comparison runs in float32, and the
    ``INF / 2`` threshold keeps the sentinel provable under quantization —
    bf16 rounds the 1e9 sentinel to ~9.98e8 and the int16 grid leaves it
    untouched by construction, both comfortably above 5e8, while any REAL
    path cost that neared 5e8 would long since have overflowed the latency
    model's scale.  Always returns float32.
    """
    d = d.astype(jnp.float32)
    finite = d < INF / 2
    sizes = jnp.sum(finite, axis=1)
    anchor = jnp.argmax(sizes)          # a node in the largest component
    mask = finite[anchor]
    pair = mask[:, None] & mask[None, :]
    return jnp.max(jnp.where(pair, d, 0.0))


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def diameter(adj: jnp.ndarray, *, use_kernel: bool = False) -> jnp.ndarray:
    """Weighted diameter of the largest connected component (paper §IV-C)."""
    return largest_cc_diameter(apsp(adj, use_kernel=use_kernel))


def diameter_of_rings(w: np.ndarray, perms: Sequence[np.ndarray]) -> float:
    """Diameter of the union-of-rings overlay, via the JAX path."""
    return float(diameter(jnp.asarray(adjacency_from_rings(w, perms))))


# ---------------------------------------------------------------------------
# scipy oracle (host)
# ---------------------------------------------------------------------------

def diameter_scipy(adj: np.ndarray) -> float:
    """Host-side oracle: Dijkstra over the sparse overlay."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components, dijkstra

    adj = np.asarray(adj, dtype=np.float64)
    sp = csr_matrix(np.where(is_edge(adj), adj, 0.0))
    ncomp, labels = connected_components(sp, directed=False)
    if ncomp > 1:
        largest = np.bincount(labels).argmax()
        keep = np.flatnonzero(labels == largest)
        sp = sp[np.ix_(keep, keep)]
    dist = dijkstra(sp, directed=False)
    return float(dist.max())
