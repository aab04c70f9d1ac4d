"""Production mesh construction, with DGRO-optimized device ordering.

``make_production_mesh`` builds the assignment's meshes:
  * single-pod: (16, 16) over ("data", "model") — 256 chips;
  * multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips.

**DGRO integration (the paper's technique as a first-class feature).**  The
axes that cross hosts/pods (``pod`` and the host-level fraction of ``data``)
run their ring-reduce collectives and the gossip membership plane over DCN,
where the hop order is software-chosen.  ``dgro_host_order`` optimizes that
order: given a host-to-host latency matrix (measured via Alg. 3's gossip
sampling in production; modeled here), it applies the paper's §V selection
(rho -> random vs nearest ring; DQN ordering available via
``repro.core.qlearning`` for small fleets) and returns the host permutation
that minimizes ring diameter.  ``make_production_mesh(dgro_order=True)``
permutes the devices of the DCN-facing axes accordingly, leaving the
intra-pod ICI order untouched (fixed torus — DESIGN.md §5).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from repro.compat import make_mesh
from repro.core.construction import nearest_ring, random_ring
from repro.core.diameter import adjacency_from_rings, diameter_scipy
from repro.core.selection import (clustering_ratio, measure_latency_stats,
                                  select_ring_kind)


def dgro_host_order(latency: np.ndarray, seed: int = 0,
                    eps: float = 0.3) -> Tuple[np.ndarray, dict]:
    """DGRO ring order for ``n`` hosts given a latency matrix.

    Applies the paper's adaptive selection: measure rho on a probe (random)
    ring; if the latency field is informative (rho high) use the nearest
    ring, otherwise keep the random ring.  Returns (order, report)."""
    n = latency.shape[0]
    rng = np.random.default_rng(seed)
    probe = random_ring(rng, n)
    adj = adjacency_from_rings(latency, [probe])
    stats = measure_latency_stats(latency, adj, seed=seed)
    rho = clustering_ratio(stats)
    kind = select_ring_kind(rho, eps)
    candidates = {"random": probe}
    if kind in ("nearest", "keep"):
        candidates["nearest"] = nearest_ring(latency, start=0)
    best_kind, best_order, best_diam = None, None, float("inf")
    for k, order in candidates.items():
        d = diameter_scipy(adjacency_from_rings(latency, [order]))
        if d < best_diam:
            best_kind, best_order, best_diam = k, order, d
    report = {
        "rho": rho, "selected": best_kind, "diameter": best_diam,
        "random_diameter": diameter_scipy(adjacency_from_rings(latency, [probe])),
    }
    return best_order, report


def model_dcn_latency(n_hosts: int, n_pods: int = 1, seed: int = 0) -> np.ndarray:
    """Synthetic DCN host latency model: intra-pod ~10us, cross-pod ~80us,
    plus per-host jitter — the stand-in for Alg. 3 measurements on CPU."""
    rng = np.random.default_rng(seed)
    pod_of = np.arange(n_hosts) // max(1, n_hosts // n_pods)
    base = np.where(pod_of[:, None] == pod_of[None, :], 10.0, 80.0)
    jitter = rng.gamma(2.0, 1.5, size=(n_hosts, n_hosts))
    lat = np.triu(base + jitter, 1)
    lat = lat + lat.T
    np.fill_diagonal(lat, 0.0)
    return lat.astype(np.float32)


def make_eval_mesh(n: Optional[int] = None, axis: str = "batch"):
    """1D mesh over the local devices for sharded bulk evaluation.

    The batch-evaluation counterpart of ``make_production_mesh``: candidate
    scoring has no model axis, so ``batcheval.diameters_sharded`` /
    ``apsp_rowshard`` just want every chip on one named axis.  ``n`` caps
    the device count (tests pin it under
    ``--xla_force_host_platform_device_count``)."""
    devices = jax.devices()
    k = min(n or len(devices), len(devices))
    return make_mesh((k,), (axis,), devices=devices[:k])


def make_production_mesh(*, multi_pod: bool = False, dgro_order: bool = False,
                         latency: Optional[np.ndarray] = None,
                         chips_per_host: int = 4):
    """The assignment's production mesh (optionally DGRO-ordered).

    With ``dgro_order``, hosts (groups of ``chips_per_host`` consecutive
    devices) are permuted along the leading (DCN-facing) axes by the DGRO
    ring; the trailing ``model`` axis stays in hardware order (ICI torus).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if not dgro_order:
        return make_mesh(shape, axes)

    devices = np.asarray(jax.devices())
    n = int(np.prod(shape))
    assert len(devices) >= n, (len(devices), n)
    devices = devices[:n]
    # hosts along the DCN-facing axes: leading dims except the model axis
    n_model = shape[-1]
    n_dcn = n // n_model                       # pod*data groups
    n_hosts = max(1, n_dcn // max(1, chips_per_host // 1))
    hosts = n_dcn                              # treat each data-group as a host
    lat = latency if latency is not None else model_dcn_latency(
        hosts, n_pods=shape[0] if multi_pod else 1)
    order, report = dgro_host_order(lat)
    grid = devices.reshape(n_dcn, n_model)
    grid = grid[order]                         # DGRO permutation of DCN axis
    dev = grid.reshape(shape)
    mesh = Mesh(dev, axes)
    mesh.dgro_report = report                  # type: ignore[attr-defined]
    return mesh
