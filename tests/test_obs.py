"""Observability stack: registry semantics, quantile math, concurrency,
structured logging, and the live /v1/metrics scrape.

Covers the PR's satellites explicitly:

* duplicate registration — identical spec returns the SAME instrument,
  conflicting type/help/labels/buckets raise at registration time;
* histogram quantile estimates stay within the containing bucket's width of
  ``np.quantile`` over the same samples (property test);
* concurrent counter increments from N threads sum exactly (no lost
  updates);
* ``GET /v1/metrics`` answers while a re-optimization cycle is in flight,
  and scraped counters match the workload exactly;
* durations come from the monotonic clock — a wall-clock step cannot
  corrupt ``uptime_s``;
* spans nest on the profiler's clock: in a CPU trace a route request's
  ``repro.state.route`` encloses its ``repro.dist.column`` on one thread,
  and importing ``repro.obs`` loads no jax.
"""
from __future__ import annotations

import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import subproc_env
from repro import serde
from repro.dynamics.scenarios import Event, Trace, poisson_churn
from repro.obs import (REGISTRY, Histogram, MetricsRegistry, TimedRLock,
                       current_span, jit_span, parse_prometheus, span)
from repro.obs.logsetup import KVFormatter, configure, get_logger, kv
from repro.service import (Reoptimizer, ServiceClient, ServiceServer,
                           ServiceState)

N0 = 20


def _world(n0=N0, dist="bitnode", seed=3) -> Trace:
    return Trace(n0=n0, capacity=2 * n0, dist=dist, seed=seed,
                 events=[], name="obs-world")


def _events(n0=N0, seed=3, events=20):
    tr = poisson_churn(n0=n0, dist="bitnode", seed=seed, horizon=30_000.0,
                       join_rate=events / 2 / 30_000.0,
                       leave_rate=events / 2 / 30_000.0)
    return sorted(tr.events, key=lambda e: e.time)[:events]


# ---------------------------------------------------------------------------
# registration semantics (satellite)
# ---------------------------------------------------------------------------

def test_same_spec_registration_returns_existing_instrument():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "things", labels=("k",))
    b = reg.counter("x_total", "things", labels=("k",))
    assert a is b
    h1 = reg.histogram("h_seconds", "hh", buckets=(1.0, 2.0))
    h2 = reg.histogram("h_seconds", "hh", buckets=(1.0, 2.0))
    assert h1 is h2


def test_conflicting_registration_raises_at_registration_time():
    reg = MetricsRegistry()
    reg.counter("x_total", "things", labels=("k",))
    with pytest.raises(ValueError):
        reg.gauge("x_total", "things")           # different kind
    with pytest.raises(ValueError):
        reg.counter("x_total", "other help", labels=("k",))
    with pytest.raises(ValueError):
        reg.counter("x_total", "things")         # different labels
    reg.histogram("h_seconds", "hh", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("h_seconds", "hh", buckets=(1.0, 2.0, 3.0))


def test_bad_histogram_buckets_rejected():
    with pytest.raises(ValueError):
        Histogram("h", buckets=())
    with pytest.raises(ValueError):
        Histogram("h", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h", buckets=(1.0, 1.0))


# ---------------------------------------------------------------------------
# quantile math vs numpy (property test, satellite)
# ---------------------------------------------------------------------------

def _bucket_tolerance(buckets, samples, value):
    """The histogram's resolution at ``value``: the containing bucket's
    width (clamp slack past the last bound)."""
    bounds = list(buckets)
    if value > bounds[-1]:
        return float(np.max(samples)) - bounds[-1] + 1e-9
    hi = next(b for b in bounds if value <= b)
    lo = max([float(np.min(samples))] + [b for b in bounds if b < hi])
    return max(hi - lo, 0.0) + 1e-9


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 300), st.floats(0.0, 1.0))
def test_histogram_quantile_within_bucket_of_numpy(seed, q):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 400))
    samples = rng.gamma(2.0, 0.05, size=n)       # spans several buckets
    h = Histogram("q_seconds")
    for s in samples:
        h.observe(float(s))
    est = h.quantile(q)
    true = float(np.quantile(samples, q, method="inverted_cdf"))
    assert float(np.min(samples)) <= est <= float(np.max(samples))
    assert abs(est - true) <= _bucket_tolerance(h.buckets, samples, true)


def test_histogram_summary_and_empty_quantile():
    h = Histogram("s_seconds")
    assert np.isnan(h.quantile(0.5))
    for v in (0.003, 0.004, 0.2):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["sum"] == pytest.approx(0.207)
    with pytest.raises(ValueError):
        h.quantile(1.5)


# ---------------------------------------------------------------------------
# thread-safety: concurrent increments sum exactly (satellite)
# ---------------------------------------------------------------------------

def test_concurrent_counter_increments_sum_exactly():
    reg = MetricsRegistry()
    ctr = reg.counter("hits_total", "hits")
    lab = reg.counter("lhits_total", "labelled hits", labels=("who",))
    hist = reg.histogram("obs_seconds", "observations")
    n_threads, per_thread = 8, 2_000

    def work(i):
        child = lab.labels(who=f"t{i % 2}")
        for _ in range(per_thread):
            ctr.inc()
            child.inc()
            hist.observe(0.01)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert ctr.value == total
    assert (lab.labels(who="t0").value + lab.labels(who="t1").value) == total
    assert hist.count == total


# ---------------------------------------------------------------------------
# spans + jit-aware timing
# ---------------------------------------------------------------------------

def test_span_nesting_and_counts():
    reg = MetricsRegistry()
    assert current_span() is None
    with span("outer", registry=reg):
        assert current_span() == "outer"
        with span("inner", registry=reg):
            assert current_span() == "inner"
        assert current_span() == "outer"
    assert current_span() is None
    h = reg.get("repro_span_seconds")
    assert h.labels(span="outer").count == 1
    assert h.labels(span="inner").count == 1


def test_jit_span_splits_compile_from_execute():
    reg = MetricsRegistry()
    for _ in range(3):
        with jit_span("fn.a", key=(4, 4), registry=reg):
            pass
    with jit_span("fn.a", key=(8, 8), registry=reg):  # retrace: new key
        pass
    comp = reg.get("repro_jit_compile_seconds").labels(fn="fn.a")
    execd = reg.get("repro_jit_execute_seconds").labels(fn="fn.a")
    assert comp.count == 2          # one first-call per distinct key
    assert execd.count == 2


def test_disabled_registry_records_nothing():
    reg = MetricsRegistry()
    ctr = reg.counter("c_total", "c")
    hist = reg.histogram("h_seconds", "h")
    reg.set_enabled(False)
    ctr.inc()
    hist.observe(1.0)
    with span("quiet", registry=reg):
        pass
    reg.set_enabled(True)
    assert ctr.value == 0 and hist.count == 0
    assert reg.get("repro_span_seconds") is None or \
        reg.get("repro_span_seconds").labels(span="quiet").count == 0


def test_timed_rlock_reentrant_and_records_waits():
    reg = MetricsRegistry()
    lock = TimedRLock(registry=reg, name="w_seconds", help="w")
    with lock:
        with lock:                  # re-entrant acquire must not deadlock
            pass
    hist = reg.get("w_seconds")
    assert hist.count == 1          # only the top-level acquire is observed

    entered = threading.Event()
    release = threading.Event()

    def holder():
        with lock:
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=holder)
    t.start()
    assert entered.wait(5.0)
    t2_done = threading.Event()

    def waiter():
        with lock:
            t2_done.set()

    t2 = threading.Thread(target=waiter)
    t2.start()
    time.sleep(0.05)
    release.set()
    t.join()
    assert t2_done.wait(5.0)
    t2.join()
    assert hist.count == 3
    assert hist.quantile(1.0) >= 0.01   # the contended acquire waited


def test_timed_rlock_hold_labelled_by_acquiring_span():
    reg = MetricsRegistry()
    hold = reg.histogram("hold_seconds", "h", labels=("holder",))
    lock = TimedRLock(registry=reg, name="w_seconds", help="w", hold=hold)
    with span("state.ingest", registry=reg):
        with lock:
            with span("dist.to_host", registry=reg), lock:  # re-entrant
                time.sleep(0.01)
    with lock:                          # no span open: "other"
        pass
    assert hold.labels(holder="state.ingest").count == 1
    assert hold.labels(holder="state.ingest").sum >= 0.01
    assert hold.labels(holder="other").count == 1
    assert hold.labels(holder="dist.to_host").count == 0
    assert reg.get("w_seconds").count == 2


def _repro_events(trace_dir: str):
    """``{thread line: [(name, start_ns, end_ns)]}`` of the ``repro.*``
    events on the host plane of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(files) == 1, files
    lines = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("repro.")]
            if evs:
                lines[(plane.name, i)] = evs
    return lines


def test_route_spans_nest_on_the_profiler_clock(tmp_path):
    import jax
    state = ServiceState.fresh(_world(), policy="dgro", seed=0)
    server = ServiceServer(state, reopt_enabled=False).start()
    try:
        c = ServiceClient(server.url)
        c.wait_ready(timeout=30)
        c.post_events(_events(events=6))     # a mutation before the route
        nodes = c.adjacency()["nodes"]
        jax.profiler.start_trace(str(tmp_path))
        try:
            c.route(nodes[0], nodes[-1])
        finally:
            jax.profiler.stop_trace()
    finally:
        server.stop(final_snapshot=False)

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    nested = []
    for evs in _repro_events(str(tmp_path)).values():
        by = {}
        for ev in evs:
            by.setdefault(ev[0], []).append(ev)
        nested += [(h, r, d)
                   for h in by.get("repro.http.route", [])
                   for r in by.get("repro.state.route", [])
                   for d in by.get("repro.dist.column", [])
                   if inside(r, h) and inside(d, r)]
    assert len(nested) == 1, _repro_events(str(tmp_path))


def test_importing_obs_loads_no_jax():
    code = ("import sys\n"
            "import repro.obs as obs\n"
            "with obs.span('x'), obs.annotate('y'):\n"
            "    pass\n"
            "lock = obs.TimedRLock(name='t_seconds', help='t')\n"
            "with lock:\n"
            "    pass\n"
            "print('JAX', 'jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=subproc_env(), cwd=".", timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "JAX False", out.stdout


# ---------------------------------------------------------------------------
# exposition: render -> parse roundtrip, JSON export
# ---------------------------------------------------------------------------

def test_prometheus_render_parse_roundtrip():
    reg = MetricsRegistry()
    reg.counter("ev_total", "events", labels=("kind",)).labels(
        kind="join").inc(3)
    reg.gauge("temp", "temperature").set(4.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.render_prometheus()
    parsed = parse_prometheus(text)
    assert parsed["ev_total"][(("kind", "join"),)] == 3.0
    assert parsed["temp"][()] == 4.5
    assert parsed["lat_seconds_bucket"][(("le", "0.1"),)] == 1.0
    assert parsed["lat_seconds_bucket"][(("le", "1"),)] == 2.0     # cumulative
    assert parsed["lat_seconds_bucket"][(("le", "+Inf"),)] == 2.0
    assert parsed["lat_seconds_count"][()] == 2.0


def test_gauge_callback_read_at_scrape_time():
    reg = MetricsRegistry()
    box = {"v": 1.0}
    reg.gauge("live", "live value").set_function(lambda: box["v"])
    assert parse_prometheus(reg.render_prometheus())["live"][()] == 1.0
    box["v"] = 7.0
    assert parse_prometheus(reg.render_prometheus())["live"][()] == 7.0


def test_render_json_is_schema_stamped():
    reg = MetricsRegistry()
    reg.counter("c_total", "c").inc()
    doc = serde.loads(reg.render_json(), what="metrics json")
    assert doc["schema"] == serde.SCHEMA_VERSION
    m = doc["metrics"]["c_total"]
    assert m["kind"] == "counter"
    assert m["series"][0]["value"] == 1.0


# ---------------------------------------------------------------------------
# structured logging
# ---------------------------------------------------------------------------

def test_kv_formatting_quotes_and_types():
    line = kv("reopt.cycle", outcome="swapped", n=3, ratio=0.25,
              ok=True, msg='has space and "quote"')
    assert line.startswith("event=reopt.cycle ")
    assert "outcome=swapped" in line and "n=3" in line
    assert "ratio=0.25" in line and "ok=true" in line
    assert 'msg="has space and \\"quote\\""' in line


def test_log_level_env_and_formatter(monkeypatch):
    monkeypatch.setenv("REPRO_LOG_LEVEL", "debug")
    configure(force=True)
    log = get_logger("test")
    assert log.name == "repro.test"
    assert logging.getLogger("repro").level == logging.DEBUG
    rec = logging.LogRecord("repro.test", logging.INFO, __file__, 1,
                            kv("unit.test", n=1), None, None)
    out = KVFormatter().format(rec)
    assert "level=info" in out and "logger=repro.test" in out
    assert "event=unit.test n=1" in out
    monkeypatch.delenv("REPRO_LOG_LEVEL")
    configure(force=True)           # restore the library default


# ---------------------------------------------------------------------------
# monotonic clock discipline (satellite)
# ---------------------------------------------------------------------------

def test_uptime_survives_wall_clock_step(monkeypatch):
    state = ServiceState.fresh(_world(), policy="rapid", seed=0)
    u0 = state.uptime_s
    # a wall-clock step (NTP, suspend) must not corrupt uptime
    monkeypatch.setattr(time, "time", lambda: 0.0)
    u1 = state.uptime_s
    assert 0.0 <= u0 <= u1 < 60.0
    assert state.stats()["uptime_s"] < 60.0


# ---------------------------------------------------------------------------
# the live scrape: /v1/metrics under load (satellite)
# ---------------------------------------------------------------------------

def test_metrics_endpoint_scrape_under_inflight_reopt():
    state = ServiceState.fresh(_world(), policy="dgro", seed=0)
    server = ServiceServer(state, reopt_enabled=False).start()
    try:
        c = ServiceClient(server.url)
        c.wait_ready(timeout=30)
        before = c.metrics()

        evs = _events(events=20)
        res = c.post_events(evs)
        assert res["accepted"] == len(evs)
        c.stats()

        reopt = Reoptimizer(state, every=2**31, eps=0.49, seed=0)
        worker = threading.Thread(target=reopt.step, kwargs={"force": True})
        worker.start()
        scrapes = 0
        while worker.is_alive():    # scrape WHILE the cycle is in flight
            after = c.metrics()
            scrapes += 1
        worker.join()
        assert scrapes > 0, "reopt finished before any scrape landed"
        after = c.metrics()

        def delta(series, **labels):
            key = tuple(sorted(labels.items()))
            return (after.get(series, {}).get(key, 0.0)
                    - before.get(series, {}).get(key, 0.0))

        assert delta("repro_service_events_ingested_total") == len(evs)
        assert delta("repro_http_requests_total", method="POST",
                     endpoint="events", status="200") == 1
        # gauges read live state: version/staleness/live-count exported
        st_now = c.stats()
        assert after["repro_service_overlay_version"][()] == st_now["version"]
        assert after["repro_service_n_live"][()] == st_now["n_live"]
        assert (after["repro_service_stale_entries"][()]
                == st_now["pending_deletions"])
        # the reopt cycle left spans + an outcome counter behind
        outcomes = after.get("repro_reopt_cycles_total", {})
        assert sum(outcomes.values()) >= sum(
            before.get("repro_reopt_cycles_total", {}).values()) + 1
        # JSON flavour of the same endpoint is schema-stamped
        doc = serde.loads(_metrics_json(c), what="metrics json")
        assert doc["schema"] == serde.SCHEMA_VERSION
    finally:
        server.stop(final_snapshot=False)


def _metrics_json(c: ServiceClient) -> str:
    import urllib.request
    with urllib.request.urlopen(f"{c.base_url}/v1/metrics?format=json",
                                timeout=30) as resp:
        return resp.read().decode()


def test_http_request_latency_histogram_counts_requests():
    state = ServiceState.fresh(_world(), policy="rapid", seed=0)
    server = ServiceServer(state, reopt_enabled=False).start()
    try:
        c = ServiceClient(server.url)
        c.wait_ready(timeout=30)
        before = c.metrics()
        for _ in range(5):
            c.stats()
        after = c.metrics()
        key = (("endpoint", "stats"),)
        d = (after["repro_http_request_seconds_count"][key]
             - before.get("repro_http_request_seconds_count", {}).get(key, 0))
        assert d == 5
    finally:
        server.stop(final_snapshot=False)
