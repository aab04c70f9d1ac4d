"""Batched greedy routing over an overlay — one jit'd device call.

Diameter says how good an overlay *could* be; greedy routing says how good
it *is* to a node that only knows its neighbours plus a per-destination
potential.  This module routes a ``(P, 2)`` batch of source/destination
pairs in ONE device call: a fixed-length ``lax.scan`` over the hop budget
whose per-step advance is ``vmap``-ed across the pair batch, with masked
termination — delivered and dead-ended pairs freeze while the rest keep
walking, and a batch-wide ``lax.cond`` skips the remaining steps entirely
once every pair has settled (the scan length never changes, so neither
does the compiled program).  Each hop scores only a degree-packed
neighbour table (:func:`_neighbor_table`), so per-hop work scales with
the overlay degree rather than N.

Two next-hop policies, selected statically:

* ``"ring"`` — Papillon-style ring-distance greedy: hop to the neighbour
  minimising circular distance to the destination on the base ring,
  requiring strict progress (so routing on any overlay that embeds the
  full ring always terminates and succeeds — the ±1 ring edges always
  make progress).
* ``"latency"`` — potential descent on ``adj[u, v] + D[v, dst]`` where
  ``D`` is a distance matrix honouring the ``dynamics.incremental``
  contract: exact, or an elementwise LOWER bound (between deletion-
  triggered rebuilds).  With an exact ``D`` the descent follows a
  shortest path (stretch exactly 1); with a stale lower bound it can
  wander, which the hop budget and per-pair failure flags absorb.

The numpy reference (:func:`route_single_host` / :func:`route_pairs_host`)
applies the *identical* float32 decision rule, so the fig19 parity gate
can assert hop/latency equality bit-for-bit — and it doubles as the one
shared implementation ``repro.service``'s ``/v1/route`` serves paths from.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.diameter import INF

__all__ = [
    "POLICIES",
    "RouteResult",
    "ring_positions",
    "ring_distance_keys",
    "latency_keys",
    "route_pairs",
    "route_overlay",
    "route_single_host",
    "route_pairs_host",
]

#: next-hop policies, in the order fig19 reports them
POLICIES = ("ring", "latency")

# score assigned to non-edges / useless hops; must stay above any real
# ``adj + D`` sum (each < INF) yet well inside float32 range
_BLOCKED = np.float32(4.0) * INF
_HALF_INF = float(INF) / 2


@dataclasses.dataclass(frozen=True)
class RouteResult:
    """Per-pair outcome of one batched routing call.

    ``stretch`` is path latency over the APSP optimum between the
    endpoints: exactly 1.0 for an optimal route, NaN for pairs that were
    not delivered (or whose optimum is unknown/INF).  ``failed`` marks
    dead ends (no useful neighbour); pairs that are neither delivered nor
    failed ran out of hop budget.
    """

    pairs: np.ndarray      # (P, 2) intp src/dst
    hops: np.ndarray       # (P,) int32
    latency: np.ndarray    # (P,) float32 accumulated path latency
    success: np.ndarray    # (P,) bool delivered
    failed: np.ndarray     # (P,) bool dead-ended (vs budget-exhausted)
    optimum: np.ndarray    # (P,) float32 APSP d(src, dst)
    stretch: np.ndarray    # (P,) float32; NaN unless delivered

    @property
    def n_pairs(self) -> int:
        return int(self.pairs.shape[0])

    def outcome(self, p: int) -> str:
        if self.success[p]:
            return "delivered"
        return "dead_end" if self.failed[p] else "exhausted"


# ---------------------------------------------------------------------------
# per-destination potentials ("keys")
# ---------------------------------------------------------------------------

def ring_positions(ring: np.ndarray) -> np.ndarray:
    """``pos[node] = index of node on the ring`` for a ring permutation."""
    ring = np.asarray(ring, np.intp)
    pos = np.empty(ring.shape[0], np.intp)
    pos[ring] = np.arange(ring.shape[0])
    return pos


def ring_distance_keys(ring: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """(P, N) circular ring distance from every node to each pair's dst."""
    pos = ring_positions(ring)
    n = pos.shape[0]
    delta = np.abs(pos[None, :] - pos[np.asarray(dst, np.intp)][:, None])
    return np.minimum(delta, n - delta).astype(np.float32)


def latency_keys(dist: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """(P, N) lower-bound distance from every node to each pair's dst."""
    return np.asarray(dist, np.float32)[:, np.asarray(dst, np.intp)].T


def _keys_for(policy: str, dst: np.ndarray, dist: Optional[np.ndarray],
              ring: Optional[np.ndarray]) -> np.ndarray:
    if policy == "latency":
        if dist is None:
            raise ValueError("latency policy needs the distance matrix")
        return latency_keys(dist, dst)
    if policy == "ring":
        if ring is None:
            raise ValueError("ring policy needs a base ring permutation")
        return ring_distance_keys(ring, dst)
    raise ValueError(f"unknown routing policy {policy!r}; options {POLICIES}")


# ---------------------------------------------------------------------------
# the device router
# ---------------------------------------------------------------------------

def _neighbor_table(adj: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Pack an (N, N) adjacency into a padded neighbour table.

    Returns ``(nbr_idx (N, D) int32, nbr_w (N, D) float32)`` with D the
    max degree: row u lists u's neighbours in ASCENDING node order (so a
    first-min argmin over the row breaks score ties exactly like the host
    reference's argmin over all N nodes) and their edge latencies, padded
    with ``_BLOCKED`` weights.  The device scan's per-hop work then
    scales with the overlay degree, not with N.
    """
    adj = np.asarray(adj, np.float32)
    n = adj.shape[0]
    edge = (adj > 0) & (adj < _HALF_INF)
    d = max(int(edge.sum(axis=1).max(initial=0)), 1)
    # stable argsort of ~edge floats edges first, ascending node order
    order = np.argsort(~edge, axis=1, kind="stable")[:, :d].astype(np.int32)
    valid = np.take_along_axis(edge, order, axis=1)
    w = np.take_along_axis(adj, order, axis=1)
    return order, np.where(valid, w, np.float32(_BLOCKED))


def _advance_one(nbr_idx, nbr_w, policy: str, key_row, cur, lat, hops, done,
                 failed):
    """One greedy hop for ONE pair (vmapped over the batch by the scan
    body).  ``key_row`` is the pair's (N,) potential toward its dst.

    Scores only the ≤ D packed neighbours of ``cur``.  Real-edge scores
    are bit-identical to the host reference's dense
    ``where(edge, adj + key, BLOCKED)`` row — pad entries differ
    (``_BLOCKED + key`` vs ``_BLOCKED``) but both stay ``>= _HALF_INF``,
    and a pad argmin winner only occurs on the stuck branch where the
    index is discarded; the ascending-node-order packing preserves the
    first-min tie break.
    """
    cands = nbr_idx[cur]                                  # (D,)
    wrow = nbr_w[cur]                                     # (D,)
    if policy == "latency":
        score = wrow + key_row[cands]
    else:
        score = jnp.where(wrow < _HALF_INF, key_row[cands], _BLOCKED)
    j = jnp.argmin(score)
    nxt = cands[j]
    best = score[j]
    if policy == "latency":
        stuck = best >= _HALF_INF          # no neighbour with a finite bound
    else:
        stuck = best >= key_row[cur]       # ring greedy demands strict progress
    active = ~done & ~failed
    move = active & ~stuck
    failed = failed | (active & stuck)
    lat = lat + jnp.where(move, wrow[j], 0.0)
    hops = hops + move.astype(jnp.int32)
    cur = jnp.where(move, nxt, cur)
    return cur, lat, hops, failed


@functools.partial(jax.jit, static_argnames=("policy", "hop_budget"))
def _route_batch_jit(nbr_idx: jnp.ndarray, nbr_w: jnp.ndarray,
                     keys: jnp.ndarray, src: jnp.ndarray,
                     dst: jnp.ndarray, *, policy: str, hop_budget: int):
    """Route all P pairs in one call: fixed-length scan over the hop
    budget, per-pair advance vmapped across the batch, masked termination
    (settled pairs freeze; fully-settled batches skip the remaining steps
    through a batch-wide ``lax.cond``)."""
    p = src.shape[0]
    advance = jax.vmap(
        functools.partial(_advance_one, nbr_idx, nbr_w, policy),
        in_axes=(0, 0, 0, 0, 0, 0))

    def step(carry, _):
        cur, lat, hops, done, failed = carry

        def live(c):
            cur, lat, hops, done, failed = c
            cur, lat, hops, failed = advance(keys, cur, lat, hops, done,
                                             failed)
            done = done | (cur == dst)
            return cur, lat, hops, done, failed

        carry = jax.lax.cond(jnp.any(~done & ~failed), live, lambda c: c,
                             carry)
        return carry, None

    carry0 = (src.astype(jnp.int32), jnp.zeros((p,), jnp.float32),
              jnp.zeros((p,), jnp.int32), src == dst, jnp.zeros((p,), bool))
    (cur, lat, hops, done, failed), _ = jax.lax.scan(
        step, carry0, None, length=hop_budget)
    return hops, lat, done, failed


def _stretch(lat: np.ndarray, success: np.ndarray,
             optimum: np.ndarray) -> np.ndarray:
    out = np.full(lat.shape, np.nan, np.float32)
    ok = success & (optimum < _HALF_INF)
    pos = ok & (optimum > 0)
    out[pos] = lat[pos] / optimum[pos]
    out[ok & (optimum == 0)] = 1.0          # src == dst: trivially optimal
    return out


def route_pairs(adj: np.ndarray, dist: Optional[np.ndarray],
                pairs: np.ndarray, *, policy: str = "latency",
                ring: Optional[np.ndarray] = None,
                hop_budget: Optional[int] = None) -> RouteResult:
    """Route a (P, 2) pair batch over an adjacency in one device call.

    ``dist`` guides the ``"latency"`` policy (exact or lower bound, per
    the incremental-maintenance contract) and, when given, prices the
    stretch denominator; ``ring`` is the base ring the ``"ring"`` policy
    descends on.  ``hop_budget`` defaults to N (a strict-descent walk can
    never need more).
    """
    adj = np.asarray(adj, np.float32)
    pairs = np.asarray(pairs, np.intp).reshape(-1, 2)
    n = adj.shape[0]
    src, dst = pairs[:, 0], pairs[:, 1]
    budget = n if hop_budget is None else int(hop_budget)
    keys = _keys_for(policy, dst, dist, ring)
    nbr_idx, nbr_w = _neighbor_table(adj)
    from repro.obs import jit_span
    with jit_span("routing.route_pairs",
                  key=(pairs.shape[0], n, nbr_idx.shape[1], policy, budget)):
        hops, lat, done, failed = _route_batch_jit(
            jnp.asarray(nbr_idx), jnp.asarray(nbr_w), jnp.asarray(keys),
            jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
            policy=policy, hop_budget=budget)
    hops, lat = np.asarray(hops), np.asarray(lat)
    success, failed = np.asarray(done), np.asarray(failed)
    optimum = (latency_keys(dist, dst)[np.arange(len(src)), src]
               if dist is not None
               else np.full(len(src), np.nan, np.float32))
    return RouteResult(pairs=pairs, hops=hops, latency=lat, success=success,
                       failed=failed, optimum=optimum,
                       stretch=_stretch(lat, success, optimum))


def route_overlay(ov, pairs: np.ndarray, *, policy: str = "latency",
                  hop_budget: Optional[int] = None) -> RouteResult:
    """Route over an :class:`~repro.overlay.Overlay`: the latency policy
    descends on the overlay's exact APSP matrix (``batcheval``), the ring
    policy on its first embedded ring."""
    ring = np.asarray(ov.rings[0]) if ov.rings else None
    return route_pairs(ov.adjacency, ov.distances(), pairs, policy=policy,
                       ring=ring, hop_budget=hop_budget)


# ---------------------------------------------------------------------------
# numpy reference (parity oracle + the service's path-serving router)
# ---------------------------------------------------------------------------

def route_single_host(adj: np.ndarray, key_to_dst: np.ndarray, src: int,
                      dst: int, *, policy: str = "latency",
                      hop_budget: Optional[int] = None
                      ) -> Tuple[List[int], float, int, str]:
    """Greedy-route ONE pair on the host, recording the path.

    Applies bit-for-bit the same float32 next-hop rule as the device scan
    (same scores, same first-min tie break), so the batched router and
    this loop agree exactly on every hop.  Returns ``(path, latency,
    hops, outcome)`` with outcome one of ``"delivered"`` / ``"dead_end"``
    / ``"exhausted"``.
    """
    if policy not in POLICIES:
        raise ValueError(f"unknown routing policy {policy!r}; "
                         f"options {POLICIES}")
    adj = np.asarray(adj, np.float32)
    key = np.asarray(key_to_dst, np.float32)
    budget = adj.shape[0] if hop_budget is None else int(hop_budget)
    blocked = np.float32(_BLOCKED)
    cur, lat, hops = int(src), np.float32(0.0), 0
    path = [cur]
    if cur == int(dst):
        return path, float(lat), hops, "delivered"
    for _ in range(budget):
        adjrow = adj[cur]
        edge = (adjrow > 0) & (adjrow < _HALF_INF)
        if policy == "latency":
            score = np.where(edge, adjrow + key, blocked)
            nxt = int(np.argmin(score))
            stuck = float(score[nxt]) >= _HALF_INF
        else:
            score = np.where(edge, key, blocked)
            nxt = int(np.argmin(score))
            stuck = float(score[nxt]) >= float(key[cur])
        if stuck:
            return path, float(lat), hops, "dead_end"
        lat = np.float32(lat + adjrow[nxt])
        hops += 1
        cur = nxt
        path.append(cur)
        if cur == int(dst):
            return path, float(lat), hops, "delivered"
    return path, float(lat), hops, "exhausted"


def route_pairs_host(adj: np.ndarray, dist: Optional[np.ndarray],
                     pairs: np.ndarray, *, policy: str = "latency",
                     ring: Optional[np.ndarray] = None,
                     hop_budget: Optional[int] = None) -> RouteResult:
    """Per-pair host loop over :func:`route_single_host` — the baseline
    the fig19 speedup gate measures and the parity oracle for the
    batched router."""
    adj = np.asarray(adj, np.float32)
    pairs = np.asarray(pairs, np.intp).reshape(-1, 2)
    budget = adj.shape[0] if hop_budget is None else int(hop_budget)
    keys = _keys_for(policy, pairs[:, 1], dist, ring)
    p = pairs.shape[0]
    hops = np.zeros(p, np.int32)
    lat = np.zeros(p, np.float32)
    success = np.zeros(p, bool)
    failed = np.zeros(p, bool)
    for i, (s, d) in enumerate(pairs):
        _, lat_i, hops_i, outcome = route_single_host(
            adj, keys[i], int(s), int(d), policy=policy, hop_budget=budget)
        lat[i], hops[i] = lat_i, hops_i
        success[i] = outcome == "delivered"
        failed[i] = outcome == "dead_end"
    optimum = (latency_keys(dist, pairs[:, 1])[np.arange(p), pairs[:, 0]]
               if dist is not None else np.full(p, np.nan, np.float32))
    return RouteResult(pairs=pairs, hops=hops, latency=lat, success=success,
                       failed=failed, optimum=optimum,
                       stretch=_stretch(lat, success, optimum))
