"""Tests for the live control plane (repro.service) and schema versioning.

Covers the tentpole's core invariants:

* serde: every serialized payload is schema-stamped, legacy payloads load,
  future payloads are rejected loudly;
* the live ingest path (``ChurnEngine.process``) applies the same event
  stream as the replay path (``run``) to the same final state;
* bounded staleness: every distance served while deletions are pending is a
  LOWER bound on the exact distance;
* crash recovery: a death between the re-optimization swap and the snapshot
  commit restores to the pre-swap overlay — both in-process (crash hook)
  and as a real daemon subprocess (``REPRO_SERVICE_CRASH_AFTER_SWAP``).
"""
from __future__ import annotations

import json
import subprocess
import sys
import threading

import numpy as np
import pytest

from conftest import subproc_env
from repro import overlay, serde
from repro.core.diameter import INF
from repro.core.topology import make_latency
from repro.dynamics.engine import ChurnEngine, DGROPolicy
from repro.dynamics.scenarios import (Event, Trace, churn_with_drift,
                                      merge_traces, poisson_churn)
from repro.service import (Reoptimizer, ServiceClient, ServiceError,
                           ServiceServer, ServiceState, latest_snapshot,
                           list_snapshots, write_snapshot)

N0 = 24


def _world(n0=N0, capacity=None, dist="bitnode", seed=3) -> Trace:
    return Trace(n0=n0, capacity=capacity or 2 * n0, dist=dist, seed=seed,
                 events=[], name="test-world")


def _trace(n0=N0, seed=3, events=30) -> Trace:
    tr = poisson_churn(n0=n0, dist="bitnode", seed=seed, horizon=30_000.0,
                       join_rate=events / 2 / 30_000.0,
                       leave_rate=events / 2 / 30_000.0)
    return Trace(n0=tr.n0, capacity=tr.capacity, dist=tr.dist, seed=tr.seed,
                 events=sorted(tr.events, key=lambda e: e.time)[:events],
                 name=tr.name)


# ---------------------------------------------------------------------------
# serde: schema stamping (satellite)
# ---------------------------------------------------------------------------

def test_serde_stamps_and_roundtrips():
    s = serde.dumps({"x": 1})
    d = json.loads(s)
    assert d["schema"] == serde.SCHEMA_VERSION
    assert serde.loads(s, what="t")["x"] == 1


def test_serde_accepts_legacy_payload_without_schema():
    assert serde.loads('{"x": 2}', what="t")["x"] == 2


def test_serde_rejects_future_and_malformed_schema():
    future = json.dumps({"schema": serde.MAX_SCHEMA + 1})
    with pytest.raises(serde.SchemaError, match="only understands"):
        serde.loads(future, what="t")
    with pytest.raises(serde.SchemaError):
        serde.loads('{"schema": "banana"}', what="t")
    with pytest.raises(serde.SchemaError, match="JSON object"):
        serde.loads("[1, 2]", what="t")


def test_overlay_and_trace_json_carry_schema():
    w = make_latency("uniform", 12, seed=0)
    ov = overlay.build("chord", w, rng=np.random.default_rng(0))
    assert json.loads(ov.to_json())["schema"] == serde.SCHEMA_VERSION
    rt = overlay.Overlay.from_json(ov.to_json())
    assert np.array_equal(rt.adjacency, ov.adjacency)

    tr = _trace(events=6)
    assert json.loads(tr.to_json())["schema"] == serde.SCHEMA_VERSION
    rt2 = Trace.from_json(tr.to_json())
    assert rt2.events == tr.events

    future = dict(json.loads(tr.to_json()), schema=serde.MAX_SCHEMA + 1)
    with pytest.raises(serde.SchemaError):
        Trace.from_json(json.dumps(future))


def test_merged_churn_drift_scenario():
    tr = churn_with_drift(n0=16, seed=1, drift_steps=4)
    kinds = {e.kind for e in tr.events}
    assert "latency_drift" in kinds and {"join", "leave"} & kinds
    times = [e.time for e in tr.events]
    assert times == sorted(times)
    with pytest.raises(ValueError, match="latency world"):
        merge_traces(poisson_churn(n0=16, seed=1),
                     poisson_churn(n0=16, seed=2))


# ---------------------------------------------------------------------------
# live ingest path == replay path
# ---------------------------------------------------------------------------

def test_engine_process_matches_run_replay():
    tr = _trace(events=24)
    replayed = ChurnEngine(tr, DGROPolicy(), seed=5)
    replayed.run(record=False)

    live_world = Trace(n0=tr.n0, capacity=tr.capacity, dist=tr.dist,
                       seed=tr.seed, events=[], name=tr.name)
    live = ChurnEngine(live_world, DGROPolicy(), seed=5)
    for e in sorted(tr.events, key=lambda t: t.time):
        live.process(e)
    live.flush()

    assert np.array_equal(live.alive, replayed.alive)
    assert np.allclose(live.inc.adj, replayed.inc.adj)
    assert live.events_processed == replayed.events_processed
    assert np.isclose(live.inc.diameter(exact=True),
                      replayed.inc.diameter(exact=True))


def test_engine_process_rejects_time_travel():
    eng = ChurnEngine(_world(), DGROPolicy(), seed=0)
    eng.process(Event(time=100.0, kind="leave", node=0))
    with pytest.raises(ValueError, match="clock"):
        eng.process(Event(time=50.0, kind="leave", node=1))


# ---------------------------------------------------------------------------
# service state: queries + staleness bound
# ---------------------------------------------------------------------------

def test_state_ingest_and_query_surface():
    state = ServiceState.fresh(_world(), policy="dgro", seed=0)
    tr = _trace(events=16)
    res = state.ingest(sorted(tr.events, key=lambda e: e.time))
    assert res["accepted"] == 16 and res["applied"] >= 16

    st = state.stats()
    assert st["events_ingested"] == 16
    assert st["distances_are"] in ("exact", "lower-bound")

    adj = state.adjacency()
    assert adj["n_live"] == st["n_live"] == len(adj["nodes"])
    src, dst = adj["nodes"][0], adj["nodes"][-1]
    r = state.route(src, dst)
    assert r["reachable"] and r["distance"] > 0
    if r["path"] is not None:
        assert r["path"][0] == src and r["path"][-1] == dst
    with pytest.raises(ValueError, match="not a live node"):
        dead = next(u for u in range(state.engine.inc.capacity)
                    if u not in set(adj["nodes"]))
        state.route(src, dead)


def test_served_distances_are_lower_bounds_while_stale():
    """The bounded-staleness contract: between deletion-triggered rebuilds
    every served distance is <= the exact live distance."""
    state = ServiceState.fresh(_world(n0=20), policy="dgro",
                               rebuild_threshold=64, seed=0)
    inc = state.engine.inc
    live0 = list(inc.live_ids())
    # leave a third of the fleet without ever hitting the rebuild threshold
    t = 0.0
    for u in live0[::3]:
        t += 10.0
        state.ingest([Event(time=t, kind="leave", node=int(u))])
    assert inc.pending_deletions > 0
    assert state.stats()["distances_are"] == "lower-bound"
    assert state.diameter()["exact"] is False

    live = inc.live_ids()
    served = inc.distances[np.ix_(live, live)].copy()
    served_routes = {(int(a), int(b)): state.route(int(a), int(b))
                     for a in live[:4] for b in live[-4:] if a != b}
    inc.refresh()                      # ground truth: exact recompute
    exact = inc.distances[np.ix_(live, live)]
    assert (served <= exact + 1e-4).all(), "stale distance overestimated"
    for (a, b), r in served_routes.items():
        assert r["bound"] == "lower"
        truth = float(inc.distances[a, b])
        if r["distance"] is not None and truth < float(INF) / 2:
            assert r["distance"] <= truth + 1e-4
    assert state.stats()["distances_are"] == "exact"


# ---------------------------------------------------------------------------
# snapshots + crash recovery (satellite)
# ---------------------------------------------------------------------------

def test_snapshot_protocol_ignores_uncommitted(tmp_path):
    d = str(tmp_path)
    write_snapshot(d, 1, {"kind": "t", "x": 1})
    write_snapshot(d, 2, {"kind": "t", "x": 2})
    # a torn write: directory exists, no COMMITTED marker
    (tmp_path / "snap-000005").mkdir()
    (tmp_path / "snap-000005" / "state.json").write_text("{}")
    assert list_snapshots(d) == [1, 2]
    seq, payload = latest_snapshot(d)
    assert seq == 2 and payload["x"] == 2


def test_snapshot_restore_roundtrip(tmp_path):
    state = ServiceState.fresh(_world(), policy="dgro",
                               snapshot_dir=str(tmp_path), seed=0)
    tr = _trace(events=12)
    state.ingest(sorted(tr.events, key=lambda e: e.time))
    state.write_snapshot(reason="test")
    _, payload = latest_snapshot(str(tmp_path))
    assert payload["schema"] == serde.SCHEMA_VERSION

    restored = ServiceState.restore(str(tmp_path))
    assert restored.events_ingested == state.events_ingested
    assert np.isclose(restored.diameter(exact=True)["diameter"],
                      payload["diameter"])
    assert restored.stats()["n_live"] == state.stats()["n_live"]
    # the restored engine keeps ingesting from the restored clock
    restored.ingest([Event(time=state.engine.clock + 1.0, kind="leave",
                           node=int(restored.engine.inc.live_ids()[0]))])


class _Boom(RuntimeError):
    pass


def test_crash_between_swap_and_snapshot_restores_preswap(tmp_path):
    """Kill the service inside the torn-state window: the buffer swap
    landed in memory but the snapshot never committed.  Restore must serve
    the consistent PRE-swap overlay."""
    state = ServiceState.fresh(_world(n0=20, dist="gaussian"),
                               policy="rapid", snapshot_dir=str(tmp_path),
                               seed=0)
    state.write_snapshot(reason="baseline")
    pre_seq, pre = latest_snapshot(str(tmp_path))
    pre_version = state.version

    def boom():
        raise _Boom()

    reopt = Reoptimizer(state, every=2**31, eps=0.49, seed=0,
                        crash_hook=boom)
    crashed = False
    for _ in range(5):
        try:
            reopt.step(force=True)     # "keep" rounds never reach the hook
        except _Boom:
            crashed = True
            break
    assert crashed, "re-optimizer never swapped; cannot exercise the window"
    assert state.version == pre_version + 1          # swap landed in memory

    seq, payload = latest_snapshot(str(tmp_path))
    assert seq == pre_seq, "snapshot leaked out of the crash window"
    assert payload["version"] == pre_version

    restored = ServiceState.restore(str(tmp_path))
    assert restored.version == pre_version
    assert np.isclose(restored.diameter(exact=True)["diameter"],
                      pre["diameter"])


def test_reopt_commit_swaps_atomically_and_improves():
    state = ServiceState.fresh(_world(n0=20, dist="gaussian"),
                               policy="rapid", seed=0)
    d0 = state.diameter(exact=True)["diameter"]
    reopt = Reoptimizer(state, every=2**31, eps=0.49, seed=0)
    swapped = None
    for _ in range(5):
        swapped = reopt.step(force=True)
        if swapped:
            break
    assert swapped and swapped["edges_added"] > 0
    assert state.version >= 1
    d1 = state.diameter(exact=True)["diameter"]
    assert d1 <= d0 + 1e-5             # added edges only relax distances


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------

def test_http_server_endpoints_and_versioning():
    state = ServiceState.fresh(_world(), policy="dgro", seed=0)
    server = ServiceServer(state, reopt_enabled=False).start()
    try:
        c = ServiceClient(server.url)
        h = c.wait_ready(timeout=30)
        assert h["api_versions"] == ["v1"]
        tr = _trace(events=10)
        res = c.post_events(sorted(tr.events, key=lambda e: e.time))
        assert res["accepted"] == 10
        assert c.stats()["events_ingested"] == 10
        nodes = c.adjacency()["nodes"]
        assert c.route(nodes[0], nodes[-1])["reachable"]

        with pytest.raises(ServiceError) as ei:
            c._request("GET", "/v9/stats")
        assert ei.value.status == 404 and "v1" in str(ei.value)
        with pytest.raises(ServiceError) as ei:
            c.route(-1, 10**6)
        assert ei.value.status == 400
        # replaying an old timestamp conflicts (409), state is unharmed
        with pytest.raises(ServiceError) as ei:
            c.post_events([Event(time=0.0, kind="leave", node=nodes[0])])
        assert ei.value.status == 409
        assert c.stats()["events_ingested"] == 10
    finally:
        server.stop(final_snapshot=False)


def test_http_queries_survive_inflight_reopt():
    state = ServiceState.fresh(_world(n0=20, dist="gaussian"),
                               policy="rapid", seed=0)
    server = ServiceServer(state, reopt_enabled=False).start()
    try:
        c = ServiceClient(server.url)
        c.wait_ready(timeout=30)
        reopt = Reoptimizer(state, every=2**31, eps=0.49, seed=0)
        worker = threading.Thread(target=reopt.step, kwargs={"force": True})
        worker.start()
        answered = 0
        while worker.is_alive():
            assert c.stats()["n_live"] == 20
            answered += 1
        worker.join()
        assert answered > 0, "reopt finished before any query landed"
        assert c.health()["status"] == "ok"
    finally:
        server.stop(final_snapshot=False)


# ---------------------------------------------------------------------------
# the real daemon: env-injected crash + restart (subprocess)
# ---------------------------------------------------------------------------

def test_imports_initialize_no_backend():
    """A chip belongs to one process: a parent that only imports the
    control plane (to drive a daemon child, as ``tools/service_smoke.py``
    does) must not have created a device array and so taken the chip."""
    code = ("import repro.service, repro.hier, repro.routing, "
            "repro.dynamics.scenarios, repro.overlay\n"
            "from repro.runtime import backend_initialized\n"
            "print('INITIALIZED', backend_initialized())")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=subproc_env(), cwd=".", timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "INITIALIZED False", \
        out.stdout


def test_daemon_crash_env_and_restart_consistency(tmp_path):
    snapdir = str(tmp_path)
    base_cmd = [sys.executable, "-m", "repro.service", "--n0", "20",
                "--dist", "gaussian", "--policy", "rapid", "--port", "0",
                "--snapshot-dir", snapdir, "--reopt-eps", "0.49",
                "--reopt-every", "1000000", "--snapshot-every", "1000000"]

    def boot(extra_env):
        proc = subprocess.Popen(base_cmd, stdout=subprocess.PIPE, text=True,
                                env=subproc_env(**extra_env), cwd=".")
        line = proc.stdout.readline().strip()
        assert line.startswith("SERVING "), line
        port = dict(kv.split("=") for kv in line.split()[1:])["port"]
        client = ServiceClient(f"http://127.0.0.1:{port}")
        client.wait_ready(timeout=60)
        return proc, client

    # phase 1: seed a committed snapshot, then crash inside the window
    proc, client = boot({"REPRO_SERVICE_CRASH_AFTER_SWAP": "1"})
    try:
        client.snapshot()
        pre_seq, pre = latest_snapshot(snapdir)
        client.reoptimize()
        rc = proc.wait(timeout=120)    # os._exit(17) after the swap
        assert rc == 17, f"daemon exited {rc}, expected the injected crash"
    finally:
        if proc.poll() is None:
            proc.kill()
            pytest.fail("daemon did not crash on the injected window")
    seq, payload = latest_snapshot(snapdir)
    assert seq == pre_seq and payload["version"] == pre["version"]

    # phase 2: restart against the same snapshot dir; ServiceState.open
    # restores and must serve exactly the committed pre-crash overlay
    proc, client = boot({})
    try:
        d = client.diameter(exact=True)
        assert np.isclose(d["diameter"], pre["diameter"]), (
            d["diameter"], pre["diameter"])
        assert client.stats()["version"] == pre["version"]
        client.shutdown()
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
