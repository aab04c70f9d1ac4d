"""Chip smoke: the DGRO membership control plane, end to end, on one chip.

Run from the root of a checkout::

    python chip_smoke.py                              # on a TPU host
    JAX_PLATFORMS=cpu python chip_smoke.py --n0 256   # rehearsal on CPU

One process, no children.  It boots the ``/v1`` daemon (``ServiceState`` +
``ServiceServer``, as ``python -m repro.service`` does) in a thread of this
process, at a fleet of ``--n0`` nodes with Bitnodes latency and the
service's default slot capacity of 2 x n0, and drives it over HTTP through
``ServiceClient``:

b. stream a seeded Poisson churn trace in batches of 10, read stats, ask
   for the exact diameter, send route queries, force a re-optimization and
   wait for it, take a snapshot, shut down;
c. check every answer against a plain reference: the diameter and every
   route distance against scipy Dijkstra on the served ``/v1/adjacency``,
   the ingest count, and no failed re-optimization cycle;
d. check the min-plus kernels themselves: the blocked-FW APSP of the
   booted overlay equals its jnp twin bit for bit, one batched squaring
   step equals its oracle, and both compiled programs hold the Pallas
   kernel (``tpu_custom_call``);
e. build a ``dgro-dqn`` overlay at the fig09 shapes and check its rings
   and its diameter.

Any failed check or exception exits non-zero.  On a platform other than
``tpu`` every phase still runs, and the script then exits 1: a CPU run is a
rehearsal, never a result.  The wall times it prints are informational
(first calls, which compile, are reported apart), not a benchmark.  The
last line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.runtime import enable_compile_cache  # noqa: E402

EVENTS = 200     # churn events streamed, in batches of 10
ROUTES = 24      # route queries, each checked against Dijkstra
SEED = 0


def check(what: str, ok: bool, detail="") -> None:
    """One reference check; a failure ends the run."""
    if not ok:
        raise AssertionError(f"check failed: {what} {detail}")
    print(f"  ok  {what}", flush=True)


class Clock:
    """Per-phase wall time, printed with the device it ran on."""

    def __init__(self, kind: str):
        self.kind = kind

    def __call__(self, label: str, t0: float) -> float:
        dt = time.perf_counter() - t0
        print(f"  time {label}: {dt:.3f} s [{self.kind}]", flush=True)
        return dt


def live_graph(adjacency: dict):
    """The served ``/v1/adjacency`` as (node ids, dense (n, n) adjacency
    with INF on non-edges and a 0 diagonal)."""
    from repro.core.diameter import INF

    nodes = np.asarray(adjacency["nodes"], np.int64)
    index = {int(u): i for i, u in enumerate(nodes)}
    adj = np.full((len(nodes), len(nodes)), INF, np.float32)
    np.fill_diagonal(adj, 0.0)
    for u, v, w in adjacency["edges"]:
        adj[index[u], index[v]] = adj[index[v], index[u]] = w
    return nodes, adj


def served_path(n0: int, clock: Clock) -> np.ndarray:
    """Phases b and c; returns the booted overlay's adjacency for d."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    from repro.core.diameter import diameter_scipy, is_edge
    from repro.dynamics.scenarios import Trace, poisson_churn
    from repro.service import ServiceClient, ServiceServer, ServiceState

    rate = 1.25 * EVENTS / 2 / 30_000.0
    trace = poisson_churn(n0=n0, dist="bitnode", seed=SEED + 1,
                          horizon=30_000.0, join_rate=rate, leave_rate=rate)
    events = sorted(trace.events, key=lambda e: e.time)[:EVENTS]
    check(f"churn trace holds {EVENTS} events",
          len(events) == EVENTS, len(events))

    print(f"[b] served path: n0={n0} capacity={2 * n0} "
          f"dist=bitnode policy=dgro", flush=True)
    world = Trace(n0=n0, capacity=2 * n0, dist="bitnode",
                  seed=SEED, events=[], name="chip-smoke")
    snapdir = tempfile.mkdtemp(prefix="dgro-chip-smoke-")
    t0 = time.perf_counter()
    state = ServiceState.open(world, snapshot_dir=snapdir, policy="dgro",
                              seed=SEED)
    clock("boot (builds the overlay, first APSP compiles)", t0)
    boot_adj = state.overlay()[0].adjacency
    # re-optimization and snapshots only on request: the smoke forces one
    # of each, so the run does the same work every time
    server = ServiceServer(state, reopt_every=10**9, snapshot_every=10**9,
                           seed=SEED).start()
    try:
        client = ServiceClient(server.url, timeout=900.0)
        check("health", client.wait_ready()["status"] == "ok")

        batches = []
        t0 = time.perf_counter()
        for i in range(0, len(events), 10):
            tb = time.perf_counter()
            res = client.post_events(events[i:i + 10])
            batches.append(time.perf_counter() - tb)
            check(f"events {i}..{i + len(events[i:i + 10]) - 1} accepted",
                  res["accepted"] == len(events[i:i + 10]), res)
        clock(f"ingest {len(events)} events in {len(batches)} batches", t0)
        print(f"  ingest batch seconds: first {batches[0]:.3f}, median "
              f"{float(np.median(batches[1:] or batches)):.3f}, max "
              f"{max(batches):.3f}", flush=True)

        st = client.stats()
        check("events_ingested equals events streamed",
              st["events_ingested"] == len(events), st["events_ingested"])
        print(f"  stats: n_live={st['n_live']} "
              f"maintenance={st['maintenance']}", flush=True)

        t0 = time.perf_counter()
        dia = client.diameter(exact=True)
        clock("diameter?exact=1", t0)
        nodes, adj = live_graph(client.adjacency())
        check("adjacency covers the live fleet", len(nodes) == st["n_live"])
        t0 = time.perf_counter()
        want = diameter_scipy(adj)
        clock("reference: scipy diameter", t0)
        check("exact diameter equals scipy Dijkstra",
              dia["exact"] and np.isclose(dia["diameter"], want, rtol=1e-5),
              (dia, want))

        rng = np.random.default_rng(SEED)
        pairs = [tuple(rng.choice(len(nodes), 2, replace=False))
                 for _ in range(ROUTES)]
        t0 = time.perf_counter()
        routes = [client.route(int(nodes[s]), int(nodes[d])) for s, d in pairs]
        clock(f"{len(routes)} route queries", t0)
        srcs = sorted({s for s, _ in pairs})
        truth = dijkstra(csr_matrix(np.where(is_edge(adj), adj, 0.0)),
                         directed=False, indices=srcs)
        row = {s: i for i, s in enumerate(srcs)}
        for (s, d), r in zip(pairs, routes):
            t = truth[row[s], d]
            check(f"route {nodes[s]}->{nodes[d]} exact and equal to Dijkstra",
                  r["bound"] == "exact" and r["reachable"]
                  and np.isclose(r["distance"], t, rtol=1e-5), (r, t))

        before = sum(client.metrics().get("repro_reopt_cycles_total",
                                          {}).values())
        t0 = time.perf_counter()
        client.reoptimize()
        while sum(client.metrics().get("repro_reopt_cycles_total",
                                       {}).values()) <= before:
            if time.perf_counter() - t0 > 900:
                raise TimeoutError("forced re-optimization never finished")
            time.sleep(0.2)
        clock("forced re-optimization", t0)
        t0 = time.perf_counter()
        snap = client.snapshot()
        clock("snapshot", t0)
        check("snapshot committed", snap["seq"] >= 1, snap)

        scraped = client.metrics()
        cycles = scraped["repro_reopt_cycles_total"]
        print(f"  reopt cycles: {dict(cycles)}", flush=True)
        check("no re-optimization cycle failed",
              cycles.get((("outcome", "error"),), 0) == 0
              and server.reopt.last_error is None, server.reopt.last_error)
        for fn in sorted({dict(k)["fn"] for k in
                          scraped.get("repro_jit_compile_seconds_sum", {})}):
            key = (("fn", fn),)
            print(f"  jit {fn}: first call "
                  f"{scraped['repro_jit_compile_seconds_sum'][key]:.3f} s, "
                  f"{int(scraped.get('repro_jit_execute_seconds_count', {}).get(key, 0))} later "
                  f"calls {scraped.get('repro_jit_execute_seconds_sum', {}).get(key, 0.0):.3f} s "
                  f"[{clock.kind}]", flush=True)
        client.shutdown()
    finally:
        server.stop(final_snapshot=False)
        shutil.rmtree(snapdir, ignore_errors=True)
    return boot_adj


def kernels_ran(boot_adj: np.ndarray, on_tpu: bool, clock: Clock) -> None:
    """Phase d: the Pallas kernels against their jnp twins."""
    import jax
    import jax.numpy as jnp

    from repro.core.diameter import INF
    from repro.kernels.minplus import ops, ref

    n = boot_adj.shape[0]
    tile = ops.default_tile(n)
    pad = (-n) % tile
    print(f"[d] kernels: apsp_tiled N={n} tile={tile}", flush=True)

    def compiled_run(label, fn, *args):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        clock(f"{label} compile", t0)
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        clock(f"{label} run", t0)
        if on_tpu:
            check(f"{label} program holds the Pallas kernel",
                  "tpu_custom_call" in compiled.as_text())
        return np.asarray(out)

    d = jnp.asarray(boot_adj)
    got = compiled_run("apsp_tiled", lambda d: ops.apsp_tiled(
        d, force_kernel=True), d)
    padded = jnp.asarray(np.pad(boot_adj, ((0, pad), (0, pad)),
                                constant_values=INF))
    t0 = time.perf_counter()
    want = np.asarray(ref.apsp_tiled_ref(padded, tile))[:n, :n]
    clock("reference: apsp_tiled_ref (first call)", t0)
    check("apsp_tiled equals apsp_tiled_ref bit for bit",
          np.array_equal(got, want))

    m = 256
    b = max(1, min(4, n // m))
    a = jnp.asarray(np.stack([boot_adj[i * m:(i + 1) * m, i * m:(i + 1) * m]
                              for i in range(b)]))
    got = compiled_run(f"minplus_batched ({b}, {m}, {m})",
                       lambda a: ops.minplus_batched(a, a, force_kernel=True),
                       a)
    check("minplus_batched equals minplus_batched_ref",
          np.array_equal(got, np.asarray(ref.minplus_batched_ref(a, a))))


def dqn_constructor(clock: Clock) -> None:
    """Phase e: the DQN ring constructor at the fig09 shapes."""
    from repro import overlay
    from repro.core.construction import default_num_rings
    from repro.core.diameter import diameter_scipy
    from repro.core.topology import make_latency

    n, envs = 32, 8
    print(f"[e] dgro-dqn: N={n} E={envs}", flush=True)
    w = make_latency("uniform", n, seed=SEED)
    cfg = overlay.DGRODQNConfig(epochs=4, n_starts=envs)
    for label in ("first build (compiles)", "second build"):
        t0 = time.perf_counter()
        ov = overlay.build("dgro-dqn", w, cfg, seed=SEED)
        clock(f"dgro-dqn {label}", t0)
    k = default_num_rings(n)
    check(f"{k} rings, each a permutation of range({n})",
          ov.num_rings == k and all(np.array_equal(np.sort(r), np.arange(n))
                                    for r in ov.rings))
    check("cached diameter equals scipy Dijkstra",
          np.isclose(ov.diameter(), diameter_scipy(ov.adjacency), rtol=1e-5),
          (ov.diameter(), diameter_scipy(ov.adjacency)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n0", type=int, default=4096,
                    help="initial fleet size (slot capacity is 2 x n0)")
    args = ap.parse_args()

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[a] device: {json.dumps(device)}", flush=True)
    on_tpu = dev.platform == "tpu"
    clock = Clock(dev.device_kind)

    t0 = time.perf_counter()
    boot_adj = served_path(args.n0, clock)
    clock("phases b+c", t0)
    kernels_ran(boot_adj, on_tpu, clock)
    dqn_constructor(clock)

    if not on_tpu:
        print(f"every phase ran on {dev.platform}: a rehearsal, not a chip "
              f"run", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
