"""Incremental APSP / diameter maintenance under churn.

The static stack (``repro.core.diameter`` / ``repro.core.batcheval``)
recomputes all-pairs distances from scratch — O(N^3) per overlay.  Under
churn most events touch one node or one edge, and the distance matrix can
be repaired far cheaper:

* **edge insert** (and any latency *decrease*): the O(N^2) relaxation
  ``D' = min(D, D[:,u] + w_uv + D[v,:], D[:,v] + w_uv + D[u,:])`` is exact —
  with positive weights a new shortest path crosses the new edge at most
  once.
* **node join**: activate a tombstoned capacity slot, compute the new row
  by one min-plus vector step over the attach edges, then relax all pairs
  through the new node — O(N^2) total, exact for the same reason.
* **node leave** (and any latency *increase*): distances can only grow,
  which a relaxation cannot express.  The node is tombstoned (isolated in
  the adjacency, its distance row/col set to INF) and a bounded staleness
  counter is incremented; when accumulated deletions exceed
  ``rebuild_threshold`` a from-scratch rebuild runs.  Between rebuilds the
  matrix is a *lower bound*: stale entries may still use paths through
  departed nodes, so ``D_stale <= D_true`` elementwise — ``refresh()``
  restores exactness on demand.

The rebuild costs O(n_live^3), not O(capacity^3): a dead slot is an
isolated singleton, so every Floyd–Warshall update through it is a no-op.
It gathers the live sub-matrix on the device, padded to a multiple of
256 slots (at most the capacity), runs ``batcheval.apsp_device`` on it,
and scatters the result back into the (capacity, capacity) matrix.  Each
padded size is its own program: construction compiles the one that holds
n_live and the one above it; a rebuild in any other compiles under the
caller's lock (a fleet that grows or shrinks by more than 256 slots).

All device math is jit'd with static shapes: the state is allocated at a
fixed ``capacity`` and dead slots are isolated singletons, which the
largest-connected-component diameter rule (paper §IV-C) ignores.  The
``*_batched`` variants advance B independent scenario replicas in one
device call (vmap over the batch axis — the same grid-over-batch shape as
``kernels.minplus.minplus_batched``; the relax itself is a broadcast
min-add, so no Pallas tile is needed) and ``relax_edge_stream_batched``
folds a whole (T, B) insert trace into a single ``lax.scan``.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import batcheval
from repro.core.diameter import (INF, is_edge, largest_cc_diameter,
                                 relax_edge_update)
from repro.obs import REGISTRY, jit_span, span

__all__ = [
    "relax_edge",
    "relax_edges_batched",
    "relax_edge_stream_batched",
    "join_node",
    "join_nodes_batched",
    "tombstone",
    "tombstones_batched",
    "IncrementalDistances",
]


# ---------------------------------------------------------------------------
# jit'd pure updates (single replica + vmapped batch variants)
# ---------------------------------------------------------------------------

# Exact O(N^2) edge-insert repair.  The primitive itself lives in
# ``core.diameter`` so the DQN rollout engine (``core.rollout``) can reuse it
# as its in-scan reward update without a core -> dynamics dependency.
_relax_edge_impl = relax_edge_update


def _join_node_impl(dist: jnp.ndarray, row: jnp.ndarray,
                    u: jnp.ndarray) -> jnp.ndarray:
    """Activate node ``u`` (previously isolated) with one-hop weights ``row``
    (INF where no attach edge).  Exact: a shortest path visits u at most
    once, so u's row is one min-plus vector step over exact old distances
    and every other pair improves only via ``d(i,u) + d(u,j)``."""
    du = jnp.min(row[:, None] + dist, axis=0)      # d(u, j) over attach edges
    du = du.at[u].set(0.0)
    dist = dist.at[u, :].set(jnp.minimum(dist[u, :], du))
    dist = dist.at[:, u].set(jnp.minimum(dist[:, u], du))
    return jnp.minimum(dist, du[:, None] + du[None, :])


def _tombstone_impl(dist: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """Isolate node ``u`` in the distance matrix (INF row/col, 0 self)."""
    c = dist.shape[0]
    iso = jnp.full((c,), INF, dist.dtype).at[u].set(0.0)
    return dist.at[u, :].set(iso).at[:, u].set(iso)


relax_edge = jax.jit(_relax_edge_impl)
join_node = jax.jit(_join_node_impl)
tombstone = jax.jit(_tombstone_impl)

# batched: (B, C, C) distance stacks advanced in one device call
relax_edges_batched = jax.jit(jax.vmap(_relax_edge_impl))
join_nodes_batched = jax.jit(jax.vmap(_join_node_impl))
tombstones_batched = jax.jit(jax.vmap(_tombstone_impl))


@jax.jit
def relax_edge_stream_batched(dists: jnp.ndarray, us: jnp.ndarray,
                              vs: jnp.ndarray, ws: jnp.ndarray) -> jnp.ndarray:
    """Apply a (T, B) stream of edge inserts to (B, C, C) replicas in ONE
    device call: ``lax.scan`` over time, vmap over the batch."""
    def step(d, uvw):
        u, v, w = uvw
        return jax.vmap(_relax_edge_impl)(d, u, v, w), None

    out, _ = jax.lax.scan(step, dists, (us, vs, ws))
    return out


_cc_diameter = jax.jit(largest_cc_diameter)

# host copies of the device distance matrix (``IncrementalDistances.
# distances``): each one moves C^2 * 4 bytes, and the span ``dist.to_host``
# times it
_HOST_COPIES = REGISTRY.counter(
    "repro_dist_host_copies_total",
    "copies of the distance matrix from the device to the host")
_HOST_BYTES = REGISTRY.counter(
    "repro_dist_host_bytes_total",
    "bytes of the distance matrix copied from the device to the host")

# reads of one column of the device matrix (``IncrementalDistances.
# column``): C * 4 bytes each, timed by the span ``dist.column``
_COLUMN_READS = REGISTRY.counter(
    "repro_dist_column_reads_total",
    "columns of the distance matrix read from the device to the host")


@jax.jit
def _column(dist: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """``dist[:, j]``; ``j`` is traced, so one program serves every
    column of a capacity."""
    return jax.lax.dynamic_index_in_dim(dist, j, axis=1, keepdims=False)


# The rebuild's size m, a multiple of this many slots (or the capacity).
# At a multiple of 256, ``kernels.minplus.ops.default_tile`` tiles the
# blocked FW at 256 with no second pad.
_BUCKET = 256

_REBUILD_SLOTS = REGISTRY.counter(
    "repro_rebuild_slots_total",
    "slots each rebuild's Floyd-Warshall ran over: the live count padded "
    "to a bucket, or the capacity")
_REBUILD_UPDATES = REGISTRY.counter(
    "repro_rebuild_updates_total",
    "min-plus updates of the rebuilds' Floyd-Warshall, slots cubed")


def _rebuild_slots(n_live: int, capacity: int) -> int:
    """The rebuild's size: ``n_live`` rounded up to a whole number of
    buckets (at least one), or the capacity where that reaches it."""
    m = -(-max(n_live, 1) // _BUCKET) * _BUCKET
    return min(m, capacity)


@jax.jit
def _gather_live(adj: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """(1, m, m) sub-matrix ``adj[idx][:, idx]``.  An index past the end
    pads: an isolated singleton, INF row and column with a 0 diagonal."""
    sub = jnp.take(jnp.take(adj, idx, axis=0, mode="fill", fill_value=INF),
                   idx, axis=1, mode="fill", fill_value=INF)
    return jnp.where(jnp.diag(idx >= adj.shape[0]), 0.0, sub)[None]


@jax.jit
def _scatter_live(sub: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """(C, C) distances from the (1, m, m) live result, where ``pos[s]``
    is slot s's place in it, or past the end for a dead slot, which comes
    out an isolated singleton.  A gather by this inverse index scatters
    the result back with no sort of its m^2 target positions."""
    m = sub.shape[-1]
    out = jnp.take(jnp.take(sub[0], pos, axis=0, mode="fill", fill_value=INF),
                   pos, axis=1, mode="fill", fill_value=INF)
    return jnp.where(jnp.diag(pos >= m), 0.0, out)


# ---------------------------------------------------------------------------
# host-side stateful wrapper
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IncrementalDistances:
    """Churn-maintained overlay adjacency + APSP distance matrix.

    ``mode="incremental"`` applies the O(N^2) repairs above and amortizes
    deletions through the staleness counter; ``mode="full"`` rebuilds from
    scratch (through ``batcheval``) after every mutation — the baseline the
    ``fig16_churn`` benchmark compares against.
    """

    w: np.ndarray                    # (C, C) latency matrix, mutable
    adj: np.ndarray                  # (C, C) overlay, INF non-edges, 0 diag
    alive: np.ndarray                # (C,) bool; dead slots are isolated
    rebuild_threshold: int = 8       # deletions tolerated before a rebuild
    mode: str = "incremental"        # "incremental" | "full"

    def __post_init__(self):
        assert self.mode in ("incremental", "full"), self.mode
        self.w = np.asarray(self.w, np.float32).copy()
        self.adj = np.asarray(self.adj, np.float32).copy()
        c = self.w.shape[0]
        assert self.adj.shape == (c, c), (self.adj.shape, c)
        if self.alive is None:
            self.alive = np.ones(c, bool)
        self.alive = np.asarray(self.alive, bool).copy()
        # isolate dead slots so they are singleton components
        dead = np.flatnonzero(~self.alive)
        self.adj[dead, :] = float(INF)
        self.adj[:, dead] = float(INF)
        self.adj[np.arange(c), np.arange(c)] = 0.0
        self.pending_deletions = 0
        self.stats: Dict[str, int] = {"relaxations": 0, "joins": 0,
                                      "leaves": 0, "rebuilds": 0,
                                      "events": 0}
        self._dist: Optional[jnp.ndarray] = None
        self._host_of = None           # weakref to the _dist last read
        # run the bucket above n_live's too (the same distances, more
        # pads), so that churn across their edge compiles nothing later
        m = _rebuild_slots(self.n_live + _BUCKET, self.capacity)
        if m != _rebuild_slots(self.n_live, self.capacity):
            self._rebuild(m)
        self.rebuild()
        self.stats["rebuilds"] = 0       # the initial APSP is not churn cost
        # each column index placed on the device once, so that a column
        # read sends nothing to the device (a scalar's own transfer was
        # ~0.2 ms of a ~1.1 ms read on a TPU v5e)
        self._col_idx = jax.device_put(
            list(np.arange(self.capacity, dtype=np.int32)))
        _column(self._dist, self._col_idx[0]).block_until_ready()  # compile

    # -- queries ----------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self.w.shape[0]

    @property
    def n_live(self) -> int:
        return int(self.alive.sum())

    def live_ids(self) -> np.ndarray:
        return np.flatnonzero(self.alive)

    @property
    def distances(self) -> np.ndarray:
        """Current (C, C) distance matrix.  Exact when no deletions are
        pending; otherwise an elementwise lower bound on the live truth.

        JAX keeps the host value of each device array, so only the first
        read after a mutation copies; that copy runs in span
        ``dist.to_host`` and is counted in ``repro_dist_host_copies_total``
        and ``repro_dist_host_bytes_total``."""
        d = self._dist
        if self._host_of is not None and self._host_of() is d:
            return np.asarray(d)
        with span("dist.to_host"):
            out = np.asarray(d)
        self._host_of = weakref.ref(d)
        _HOST_COPIES.inc()
        _HOST_BYTES.inc(out.nbytes)
        return out

    def column(self, j: int) -> np.ndarray:
        """``D[:, j]`` as a (C,) float32 host array, the same values as
        ``self.distances[:, j]``: only the column crosses to the host.
        The read runs in span ``dist.column`` and is counted in
        ``repro_dist_column_reads_total``; it leaves the host copy of the
        whole matrix alone."""
        with span("dist.column"):
            out = np.asarray(_column(self._dist, self._col_idx[j]))
        _COLUMN_READS.inc()
        return out

    def live_distances(self) -> np.ndarray:
        live = self.live_ids()
        return self.distances[np.ix_(live, live)]

    def diameter(self, exact: bool = False) -> float:
        """Largest-CC diameter of the maintained overlay.  ``exact`` forces
        a rebuild first if deletions are pending."""
        if exact:
            self.refresh()
        return float(_cc_diameter(self._dist))

    # -- mutations --------------------------------------------------------

    def add_edge(self, u: int, v: int, weight: float | None = None) -> None:
        """Insert (or improve) the undirected edge (u, v)."""
        assert self.alive[u] and self.alive[v], (u, v)
        wuv = np.float32(self.w[u, v] if weight is None else weight)
        self.stats["events"] += 1
        if u == v or wuv >= self.adj[u, v]:
            return                        # no improvement: relax is a no-op
        self.adj[u, v] = self.adj[v, u] = wuv
        if self.mode == "full":
            self.rebuild()
            return
        self._dist = relax_edge(self._dist, u, v, wuv)
        self.stats["relaxations"] += 1

    def remove_edge(self, u: int, v: int) -> None:
        """Delete the undirected edge (u, v); distances may go stale."""
        self.stats["events"] += 1
        if not is_edge(np.float32(self.adj[u, v])):
            return
        self.adj[u, v] = self.adj[v, u] = float(INF)
        self._note_deletion()

    def join(self, u: int, neighbours: Sequence[int],
             weights: Sequence[float] | None = None) -> None:
        """Activate slot ``u`` and attach it to live ``neighbours``."""
        assert not self.alive[u], u
        nbrs = np.asarray(list(neighbours), np.intp)
        assert self.alive[nbrs].all(), "attach edges must target live nodes"
        ws = (self.w[u, nbrs] if weights is None
              else np.asarray(list(weights), np.float32))
        self.alive[u] = True
        self.adj[u, nbrs] = np.minimum(self.adj[u, nbrs], ws)
        self.adj[nbrs, u] = self.adj[u, nbrs]
        self.stats["events"] += 1
        self.stats["joins"] += 1
        if self.mode == "full":
            self.rebuild()
            return
        row = np.full(self.capacity, float(INF), np.float32)
        row[nbrs] = self.adj[u, nbrs]
        self._dist = join_node(self._dist, jnp.asarray(row), u)
        self.stats["relaxations"] += 1

    def leave(self, u: int) -> None:
        """Tombstone node ``u``: isolate it and count the deletion."""
        if not self.alive[u]:
            return
        self.alive[u] = False
        self.adj[u, :] = float(INF)
        self.adj[:, u] = float(INF)
        self.adj[u, u] = 0.0
        self.stats["events"] += 1
        self.stats["leaves"] += 1
        if self.mode != "full":        # full mode rebuilds anyway below
            self._dist = tombstone(self._dist, u)
        self._note_deletion()

    def set_latency(self, u: int, v: int, ms: float) -> None:
        """Point latency change; decreases relax, increases count as stale.

        The increase/decrease split compares against the CURRENT edge
        weight (``adj``, which ``add_edge`` may have set below ``w``) —
        comparing against ``w`` could misread an edge-weight increase as a
        decrease and break the lower-bound contract."""
        ms = float(ms)
        self.w[u, v] = self.w[v, u] = ms
        if not is_edge(np.float32(self.adj[u, v])):
            return
        old_edge = float(self.adj[u, v])
        self.stats["events"] += 1
        self.adj[u, v] = self.adj[v, u] = np.float32(ms)
        if self.mode == "full":
            self.rebuild()
        elif ms < old_edge:
            self._dist = relax_edge(self._dist, u, v, np.float32(ms))
            self.stats["relaxations"] += 1
        elif ms > old_edge:
            self._note_deletion()

    def apply_latency_matrix(self, new_w: np.ndarray) -> None:
        """Bulk latency change (e.g. diurnal drift): re-weight every existing
        edge and rebuild — a matrix-wide shift has no cheap exact repair."""
        new_w = np.asarray(new_w, np.float32)
        assert new_w.shape == self.w.shape
        self.w = new_w.copy()
        mask = is_edge(self.adj)
        self.adj = np.where(mask, new_w, self.adj).astype(np.float32)
        self.stats["events"] += 1
        self.rebuild()

    # -- rebuild machinery ------------------------------------------------

    def _note_deletion(self) -> None:
        self.pending_deletions += 1
        if self.mode == "full" or self.pending_deletions >= self.rebuild_threshold:
            self.rebuild()

    def rebuild(self) -> None:
        """From-scratch APSP over the live adjacency; resets the staleness
        counter.  The result stays on the device.

        Precision is PINNED to float32 regardless of ambient
        ``eval_options`` / ``REPRO_APSP_*`` overrides: the incremental
        relaxations layered on top of this matrix assume an exact base
        (every served distance is "exact or lower bound"), so a quantized
        rebuild would silently poison that contract.

        Its phases run in spans ``rebuild.to_device`` (the adjacency's
        upload) and ``rebuild.device`` (gather, Floyd–Warshall, scatter).
        Each rebuild adds its size m to ``repro_rebuild_slots_total`` and
        m^3 to ``repro_rebuild_updates_total``.
        """
        self._rebuild(_rebuild_slots(self.n_live, self.capacity))
        self.pending_deletions = 0
        self.stats["rebuilds"] += 1

    def _rebuild(self, m: int) -> None:
        """``self._dist`` from a Floyd–Warshall over ``m`` slots: the live
        ones in ascending order, so the pivots keep their order, then
        pads.  Each size m is its own program, so the span's key is
        (capacity, m): the first rebuild at a size is timed as a compile."""
        live = self.live_ids()
        idx = np.full(m, self.capacity, np.int32)
        idx[:live.size] = live
        pos = np.full(self.capacity, m, np.int32)
        pos[live] = np.arange(live.size)
        with jit_span("incremental.rebuild", key=(self.capacity, m)):
            with span("rebuild.to_device"):
                adj = jnp.asarray(self.adj).block_until_ready()
            with span("rebuild.device"):
                sub = batcheval.apsp_device(
                    _gather_live(adj, jnp.asarray(idx)))
                self._dist = jax.block_until_ready(
                    _scatter_live(sub, jnp.asarray(pos)))
        _REBUILD_SLOTS.inc(m)
        _REBUILD_UPDATES.inc(m ** 3)

    def refresh(self) -> None:
        """Restore exactness if deletions are pending."""
        if self.pending_deletions:
            self.rebuild()
