"""CI smoke: boot the control-plane daemon, stream a churn trace, shut down.

Starts ``python -m repro.service.server`` as a real subprocess, streams a
50-event poisson-churn trace through :class:`repro.service.ServiceClient`,
asserts every query endpoint answers sensibly, forces a re-optimization and
a snapshot, scrapes ``GET /v1/metrics`` and checks the counters match what
was streamed (a fresh process, so absolute values are exact), and checks
the daemon exits cleanly on ``POST /v1/shutdown``.

    PYTHONPATH=src python tools/service_smoke.py [--events 50] [--n0 32]
    PYTHONPATH=src python tools/service_smoke.py --policy dgro-hier --n0 96

With ``--policy dgro-hier`` the daemon serves a hierarchical overlay:
the same endpoint contract is asserted, plus the hier gauges
(``repro_hier_clusters``, ``repro_hier_headring_diameter``) and the
per-level ``repro_hier_route_hops`` histogram must appear in the scrape.

Run under both ``JAX_PLATFORMS=cpu`` and the default platform in CI.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.dynamics.scenarios import poisson_churn  # noqa: E402
from repro.runtime import backend_initialized  # noqa: E402
from repro.service import ServiceClient  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=50)
    ap.add_argument("--n0", type=int, default=32)
    ap.add_argument("--dist", default="bitnode")
    ap.add_argument("--policy", default="dgro",
                    help="overlay policy the daemon serves "
                         "(e.g. dgro, dgro-hier)")
    ap.add_argument("--timeout", type=float, default=120.0)
    args = ap.parse_args()
    hier = args.policy == "dgro-hier"

    # a trace with >= the requested number of events (rates scale with count)
    trace = poisson_churn(n0=args.n0, dist=args.dist, seed=1,
                          horizon=30_000.0,
                          join_rate=args.events / 2 / 30_000.0,
                          leave_rate=args.events / 2 / 30_000.0)
    events = sorted(trace.events, key=lambda e: e.time)[:args.events]
    assert len(events) >= min(args.events, 40), (
        f"trace only produced {len(events)} events")

    # a chip belongs to one process: the daemon child needs it, so this
    # parent must never have touched a JAX backend
    assert not backend_initialized(), "smoke parent initialized a JAX backend"
    snapdir = tempfile.mkdtemp(prefix="dgro-service-smoke-")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.service",
         "--n0", str(args.n0), "--capacity", str(trace.capacity),
         "--dist", args.dist, "--policy", args.policy,
         "--port", "0", "--snapshot-dir", snapdir,
         "--reopt-every", "16", "--snapshot-every", "25"],
        stdout=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONPATH": "src"})
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("SERVING "), f"unexpected boot line: {line!r}"
        port = dict(kv.split("=") for kv in line.split()[1:])["port"]
        c = ServiceClient(f"http://127.0.0.1:{port}")

        health = c.wait_ready(timeout=args.timeout)
        assert health["status"] == "ok" and "v1" in health["api_versions"]

        d0 = c.diameter()
        assert d0["diameter"] > 0 and d0["n_live"] == args.n0

        for i in range(0, len(events), 10):
            res = c.post_events(events[i:i + 10])
            assert res["applied"] >= res["accepted"] > 0, res

        st = c.stats()
        assert st["events_ingested"] == len(events), st
        assert st["n_live"] >= 4
        assert st["distances_are"] in ("exact", "lower-bound")
        if hier:
            assert st["clusters"] > 0, st
            assert st["reorg"]["head_rebuilds"] >= 0, st

        nodes = c.adjacency()["nodes"]
        assert len(nodes) == st["n_live"]
        r = c.route(nodes[0], nodes[-1])
        assert r["reachable"] and r["distance"] > 0
        assert r["path"] is None or (r["path"][0] == nodes[0]
                                     and r["path"][-1] == nodes[-1])
        # enriched routing keys (shared repro.routing router)
        if r["path"] is not None:
            assert r["hops"] == len(r["path"]) - 1, r
            # served distance is exact or a lower bound -> stretch >= 1
            assert r["stretch"] >= 1 - 1e-5, r
            assert r["hop_bounds"] == [r["bound"]] * r["hops"], r
            if hier:
                levels = r["hops_by_level"]
                assert levels["local"] + levels["head"] == r["hops"], r
        else:
            assert r["hops"] is None and r["stretch"] is None, r

        c.reoptimize()
        snap = c.snapshot()
        assert snap["seq"] >= 1, snap
        d1 = c.diameter(exact=True)
        assert d1["exact"] and d1["diameter"] > 0

        # the observability scrape: a fresh daemon process, so counters are
        # absolute — ingested events must match what this script streamed
        scraped = c.metrics()
        assert (scraped["repro_service_events_ingested_total"][()]
                == len(events)), scraped["repro_service_events_ingested_total"]
        reqs = scraped.get("repro_http_requests_total", {})
        assert sum(reqs.values()) > 0, "no HTTP requests counted"
        post_key = (("endpoint", "events"), ("method", "POST"),
                    ("status", "200"))
        assert reqs[post_key] == (len(events) + 9) // 10, reqs
        assert scraped["repro_service_n_live"][()] == st["n_live"]
        # the shared routing instruments: exactly one /v1/route was served
        # (the hier engine additionally counts its internal walk under
        # policy="hier-latency", so hier scrapes carry two series)
        route_reqs = scraped["repro_route_requests_total"]
        assert sum(route_reqs.values()) == (2 if hier else 1), route_reqs
        if r["path"] is not None:
            key = (("outcome", "delivered"), ("policy", "latency"))
            assert route_reqs[key] == 1, route_reqs
            assert scraped["repro_route_hops_count"][()] == 1, scraped

        if hier:
            # the hierarchical instruments must land in the same scrape:
            # the cluster/head-ring gauges are bound to live engine state,
            # and the delivered route above observed per-level hops
            assert scraped["repro_hier_clusters"][()] == st["clusters"] > 0, \
                scraped.get("repro_hier_clusters")
            assert scraped["repro_hier_headring_diameter"][()] >= 0, scraped
            hier_hops = scraped["repro_hier_route_hops_count"]
            local_key = (("level", "local"),)
            assert hier_hops.get(local_key, 0) >= 1, hier_hops

        # the APSP engine instruments: the forced re-optimization scored
        # candidates through batcheval, so the per-phase evaluation spans
        # and the working-set gauge must have landed in the same scrape
        apsp_counts = scraped["repro_apsp_seconds_count"]
        assert sum(apsp_counts.values()) >= 1, apsp_counts
        assert scraped["repro_apsp_workingset_bytes"][()] > 0, scraped

        c.shutdown()
        rc = proc.wait(timeout=30)
        assert rc == 0, f"daemon exited {rc}"
        out = proc.stdout.read()
        assert "STOPPED" in out, out
        print(f"OK  service smoke: {len(events)} events streamed, "
              f"n_live={st['n_live']}, diameter={d1['diameter']:.1f}, "
              f"clean shutdown")
    finally:
        if proc.poll() is None:
            proc.kill()


if __name__ == "__main__":
    main()
