"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip: it boots the ``/v1`` daemon as ``python -m
repro.service.server`` does (``ServiceState.open`` and ``ServiceServer``
with the daemon settings that the configuration states, and no snapshot
directory) in a thread, then
starts ``chipbench/loadgen.py`` as a child that imports neither ``jax``
nor ``repro`` and drives the cell's traffic over HTTP: ``warmup_s`` of the
mix, then the measured window of ``--seconds``.  After the window it reads
the served state back, stops the daemon, and checks every answer against
the plain reference (``chipbench/reference.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` it traces the window and carries the per-layer metrics, each
read by ``chipbench/metrics/<name>.py``.  The last line of standard output
is the JSON result; the numbers compared, each with its limit, are the last
lines of standard error and the result's last key, ``checks``.

Without a TPU (or with fewer chips than the cell asks for) the run exits 3
and prints no result.  ``JAX_PLATFORMS=cpu python3 chipbench/run.py
--rehearse --n0 128 ...`` runs every step on the CPU at a small fleet,
prints what it checked, and exits 1: a rehearsal, never a result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import gen, latency, reference, scrape, tracereduce  # noqa: E402
from chipbench.httpclient import Connection  # noqa: E402

ROUTE_SENDERS = 64        # concurrent route senders in the load generator
DRAIN_S = 60.0            # how long answers due in the window are awaited
SAMPLE_ROUTES = 256       # post-window route queries checked per phase

# each number compared, with its limit (PERF.md gives the readings)
LIMITS = {
    "failed": 0,                  # requests due in the window never 2xx
    "live_set_mismatch": 0,       # served live set vs acknowledged events
    "ingest_count_gap": 0,        # events_ingested vs events acknowledged
    "edge_weight_mismatch": 0,    # served edges vs the latency model
    "route_path_faults": 0,       # window paths that do not join src, dst
    "window_route_gap": 1e-5,     # window distance vs its own path latency
    "route_gap": 1e-5,            # served distance vs reference, as left
    "exact_route_gap": 1e-5,      # served distance vs reference, refreshed
    "diameter_gap": 1e-5,         # exact diameter vs reference
}

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_COMPILE_LOG: List[tuple] = []     # (monotonic time, "compile" | "hit")


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


# -- finding a cell's parts by name -------------------------------------------

def load_cell(root: str, workload: str) -> SimpleNamespace:
    """The cell's entry, its configuration, its traffic mix and the
    per-layer metrics that apply to it, all found by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    return SimpleNamespace(cell=cell, config=config, traffic=traffic,
                           end_to_end=e2e, per_layer=per_layer)


def load_reader(root: str, name: str) -> Callable:
    """``read(ctx)`` of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- JAX ------------------------------------------------------------------------

def start_jax(root: str, require_chip: bool, chips: int):
    """Place the compile cache inside the checkout, count compiles, and
    find the chips."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  os.path.join(root, ".jax_cache"))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _COMPILE_LOG:
        _COMPILE_LOG.append((0.0, "start"))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _s, **_kw: event == _BACKEND_COMPILE
            and _COMPILE_LOG.append((time.monotonic(), "compile")))
        jax.monitoring.register_event_listener(
            lambda event, **_kw: event == _CACHE_HIT
            and _COMPILE_LOG.append((time.monotonic(), "hit")))
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"found {len(devs)} {devs[0].platform} device(s); the "
                     f"cell needs {chips} TPU chip(s)")
    return jax, devs


def compiles_between(t0: float, t1: float) -> int:
    """Backend compiles in [t0, t1] that the persistent cache did not
    serve (JAX's own monitoring events)."""
    inside = [kind for t, kind in _COMPILE_LOG if t0 <= t <= t1]
    return inside.count("compile") - inside.count("hit")


def annotate_program(jax) -> None:
    """Traced runs only: name the program's entry calls in the profiler's
    trace, so that idle gaps can be labelled.  Timing is unchanged."""
    from repro.service import reoptimizer, state

    for cls, meth in ((state.ServiceState, "ingest"),
                      (state.ServiceState, "route"),
                      (state.ServiceState, "stats"),
                      (reoptimizer.Reoptimizer, "step")):
        inner = getattr(cls, meth, None)
        if inner is None or getattr(inner, "_chipbench", False):
            continue

        def wrapped(*a, _inner=inner, _label=f"chipbench.{meth}", **kw):
            with jax.profiler.TraceAnnotation(_label):
                return _inner(*a, **kw)
        wrapped._chipbench = True
        setattr(cls, meth, wrapped)


# -- the run -------------------------------------------------------------------

def _get(conn: Connection, path: str) -> dict:
    status, body = conn.request("GET", path)
    if status != 200 or body is None:
        raise RuntimeError(f"GET {path} answered {status}")
    return body


def _pct(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             control: Optional[str] = None, n0: Optional[int] = None,
             patch: Optional[Callable[[], None]] = None,
             rates: Optional[Dict[str, float]] = None) -> Dict:
    """One run of one cell; returns the result dict (see module doc).

    ``n0``, ``patch`` (called once the program is imported, before boot)
    and ``rates`` (``{"churn": events/s, "routes": queries/s}``) serve
    rehearsals, tests and the rate sweep, never a measured run.  With
    ``control`` the reference in that lower precision stands in the
    program's place for the checks (see ``_check``); the program's own
    checks are kept under ``program_checks``.
    """
    parts = load_cell(root, workload)
    config, traffic = dict(parts.config), json.loads(json.dumps(
        parts.traffic))
    if n0 is not None:
        config["n0"], config["capacity"] = int(n0), 2 * int(n0)
    for part, rate in (rates or {}).items():
        traffic[part]["rate_per_s"] = float(rate)
    jax, devs = start_jax(root, require_chip, int(parts.cell["chips"]))
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.dynamics.scenarios import Trace
    from repro.service import ServiceServer, ServiceState
    if patch is not None:
        patch()
    if trace:
        annotate_program(jax)

    sched = gen.build_schedule(config, traffic, seed, seconds)
    # the fleet itself (latency map, overlay build, the daemon's own
    # randomness) comes from the configuration's world seed, so that
    # seeds vary the traffic and not the deployment
    pseed = int(config["world_seed"])
    world = Trace(n0=config["n0"], capacity=config["capacity"],
                  dist=config["latency"], seed=pseed, events=[],
                  name=config["name"])
    state = ServiceState.open(world, snapshot_dir=None,
                              policy=config["policy"],
                              k_rings=config.get("k_rings"),
                              detect_failures=config["detect_failures"],
                              seed=pseed)
    server = ServiceServer(state, seed=pseed, **config["daemon"]).start()
    conn = Connection(server.host, server.port)
    try:
        _get(conn, "/v1/health")
        job = {"host": server.host, "port": server.port,
               "t0": time.monotonic() + 0.5, "schedule": sched,
               "route_senders": ROUTE_SENDERS, "drain_s": DRAIN_S}
        win0 = job["t0"] + sched["warmup_s"]
        win1 = win0 + seconds
        child = subprocess.Popen(
            [sys.executable, os.path.join(root, "chipbench", "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        child.stdin.write(json.dumps(job).encode())
        child.stdin.close()
        traced = _window(jax, conn, win0, win1, trace)
        raw = child.stdout.read()
        child.wait(timeout=DRAIN_S + 60)
        child.stdout.close()
        got = json.loads(raw)
        if server.reopt is not None:       # quiesce before reading back
            server.reopt.stop()
        served = _read_back(conn, seed)
        mem = devs[0].memory_stats() or {}
    finally:
        conn.close()
        server.stop(final_snapshot=False)
    del state, server
    gc.collect()

    checks = _check(config, sched, got, served, pseed)
    program_checks = None
    if control:
        program_checks = checks
        checks = _check(config, sched, got, served, pseed, control)
    window = _in_window(sched, got)
    metrics = {}
    if not trace:
        vals = {"ingest_p95_ms": _pct(window["event_ms"], 95)
                if window["event_ms"] else None,
                "route_p50_ms": _pct(window["route_ms"], 50)
                if window["route_ms"] else None,
                "route_p95_ms": _pct(window["route_ms"], 95)
                if window["route_ms"] else None,
                "setup_s": win0 - T_START}
        for m in parts.end_to_end:
            if vals.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": int(parts.cell["chips"]),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    result = {"correct": all(v <= LIMITS[k] for k, v in checks.items()),
              "attempted": window["attempted"], "failed": window["failed"],
              "metrics": metrics, "device": device}
    if trace:
        # a trace of another platform gives no device number
        red = traced["reduced"] if devs[0].platform == "tpu" else None
        ctx = SimpleNamespace(
            before=traced["scrape0"], after=traced["scrape1"],
            stats_before=traced["stats0"], stats_after=traced["stats1"],
            trace=red, compiles=compiles_between(win0, win1),
            capacity=int(config["capacity"]),
            overlay_diameter=served["diameter"]["diameter"])
        for m in parts.per_layer:
            if m["source"] == "device_trace" and red is None:
                continue
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red is not None:
            device["busy_s"], device["window_s"] = red["busy_s"], \
                red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    result["lag"] = window["lag"]
    result["spread"] = window["spread"]
    if rates is not None:
        result["samples"] = window["samples"]
    if program_checks is not None:
        result["program_checks"] = {k: {"value": v, "limit": LIMITS[k]}
                                    for k, v in program_checks.items()}
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in checks.items()}
    return result


def _window(jax, conn: Connection, win0: float, win1: float,
            trace: bool) -> Dict:
    """Wait out the window; in a traced run, trace it and scrape the
    program's counters at both ends."""
    def until(t):
        time.sleep(max(0.0, t - time.monotonic()))

    if not trace:
        until(win1)
        return {}
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        until(win0 - 1.0)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        t_tr0 = time.monotonic()
        until(win0)
        out = {"scrape0": scrape.parse(conn.raw("GET", "/v1/metrics")[1]
                                       .decode()),
               "stats0": _get(conn, "/v1/stats")}
        until(win1)
        out["scrape1"] = scrape.parse(conn.raw("GET", "/v1/metrics")[1]
                                      .decode())
        out["stats1"] = _get(conn, "/v1/stats")
        t_tr1 = time.monotonic()
        jax.profiler.stop_trace()
        files = [os.path.join(d, f) for d, _, fs in os.walk(tdir)
                 for f in fs if f.endswith(".xplane.pb")]
        rows = tracereduce.flatten(files[0]) if files else []
        out["reduced"] = (tracereduce.reduce(rows, t_tr1 - t_tr0)
                          if rows else None)
        return out
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _read_back(conn: Connection, seed: int) -> Dict:
    """The served state once the traffic has stopped: the live overlay,
    a seeded sample of routes as left by the window, the exact diameter
    (which refreshes pending deletions) and the same routes again."""
    stats = _get(conn, "/v1/stats")
    adj = _get(conn, "/v1/adjacency")
    nodes = adj["nodes"]
    rng = gen.rng_for(seed, 2)
    pairs = [tuple(int(nodes[i]) for i in rng.choice(len(nodes), 2,
                                                      replace=False))
             for _ in range(SAMPLE_ROUTES)]

    def routes():
        return [_get(conn, f"/v1/route?src={s}&dst={d}") for s, d in pairs]

    before = routes()
    dia = _get(conn, "/v1/diameter?exact=1")
    after = routes()
    return {"stats": stats, "stats_end": _get(conn, "/v1/stats"),
            "adjacency": adj, "pairs": pairs, "before": before,
            "diameter": dia, "after": after}


def _in_window(sched: Dict, got: Dict) -> Dict:
    """End-to-end samples, counts and generator lag of the window."""
    lo = sched["warmup_s"]
    hi = lo + sched["window_s"]
    ev = [(e, r) for e, r in zip(sched["events"], got["events"])
          if lo <= e[0] < hi]
    rt = [(q, r) for q, r in zip(sched["routes"], got["routes"])
          if lo <= q[0] < hi]
    ok = [r for _, r in ev if r and 200 <= r[0] < 300]
    rok = [r for _, r in rt if r and 200 <= r[0] < 300]
    lag = [r[2] for _, r in rt if r]
    samples = {"event": [[e[0] - lo, r[1]] for e, r in ev
                         if r and 200 <= r[0] < 300],
               "route": [[q[0] - lo, r[1]] for q, r in rt
                         if r and 200 <= r[0] < 300]}
    return {"samples": samples,
            "event_ms": [r[1] * 1e3 for r in ok],
            "route_ms": [r[1] * 1e3 for r in rok],
            "attempted": len(ev) + len(rt),
            "failed": len(ev) + len(rt) - len(ok) - len(rok),
            "lag": {"route_dispatch_late_ms_p50": _pct(lag, 50) * 1e3,
                    "route_dispatch_late_ms_max": max(lag) * 1e3,
                    "route_sent_late_ms_p99": _pct(
                        [r[3] for _, r in rt if r], 99) * 1e3}
            if lag else {},
            "spread": {f"{lane}_ms_p{q}": _pct(v, q)
                       for lane, v in (("event", [r[1] * 1e3 for r in ok]),
                                       ("route", [r[1] * 1e3 for r in rok]))
                       if v for q in (50, 80, 90, 95, 99)}}


def _check(config: Dict, sched: Dict, got: Dict, served: Dict, pseed: int,
           control: Optional[str] = None) -> Dict:
    """Every number compared (see LIMITS).

    With ``control`` (a dtype below float32) the plain reference computed
    in that precision stands in the program's place: its distances replace
    the served ones after the window and the diameter, each window path's
    latency summed hop by hop in that precision replaces the served
    distance, and all go through the same comparison.
    """
    window = _in_window(sched, got)
    w = latency.latency_matrix(config["latency"], config["capacity"], pseed)
    edges = served["adjacency"]["edges"]
    index, adj = reference.dense(served["adjacency"]["nodes"], edges)
    ref = reference.apsp(adj)
    routes = got["routes"]
    if control:
        low = reference.apsp(adj, control)
        ans = [{"distance": float(low[index[s], index[d]])}
               for s, d in served["pairs"]]
        served = dict(served, before=ans, after=ans,
                      diameter={"diameter": reference.cc_diameter(low)})
        routes = [r[:6] + [reference.path_sum(
                      [w[a, b] for a, b in zip(r[8], r[8][1:])], control)]
                  + r[7:] if r and r[8] else r for r in routes]

    # ingest: the served live set against the acknowledged events (the
    # mix sends no failures, so none may await confirmation)
    base, acked = set(range(config["n0"])), 0
    for (_, kind, node), r in zip(sched["events"], got["events"]):
        if not (r and 200 <= r[0] < 300):
            continue
        acked += 1
        if kind == "join":
            base.add(node)
        else:
            base.discard(node)
    mismatch = (len(set(served["adjacency"]["nodes"]) ^ base)
                + served["stats"]["pending_confirmations"])

    # served edge weights against the benchmark's own latency model
    bad_w = sum(np.float32(wt) != w[int(u), int(v)] for u, v, wt in edges)

    # window routes: each path joins src to dst, and its latency by the
    # model matches the served distance (exact) or bounds it (lower)
    faults, wgap = 0, 0.0
    lo, hi = sched["warmup_s"], sched["warmup_s"] + sched["window_s"]
    for q, r in zip(sched["routes"], routes):
        if not (lo <= q[0] < hi and r and 200 <= r[0] < 300):
            continue
        _, _, _, _, src, dst, dist, bound, path = r
        if dist is None:
            faults += 1
            continue
        if path is None:
            continue
        if path[0] != src or path[-1] != dst or len(set(path)) != len(path):
            faults += 1
            continue
        hops = [w[a, b] for a, b in zip(path, path[1:])]
        plat = float(np.sum(np.asarray(hops, np.float64)))
        wgap = max(wgap, reference.rel_gap(dist, plat, bound != "exact"))

    # the served distance matrix and diameter against the reference
    lower = served["stats"]["pending_deletions"] > 0

    def gaps(answers, lower_ok):
        out = 0.0
        for (s, d), a in zip(served["pairs"], answers):
            want = ref[index[s], index[d]]
            got_d = a["distance"]
            if got_d is None or not np.isfinite(want):
                out = max(out, 0.0 if (got_d is None) == (not np.isfinite(
                    want)) else float("inf"))
                continue
            out = max(out, reference.rel_gap(got_d, want, lower_ok))
        return out

    return {
        "failed": window["failed"],
        "live_set_mismatch": mismatch,
        "ingest_count_gap": abs(served["stats"]["events_ingested"] - acked),
        "edge_weight_mismatch": int(bad_w),
        "route_path_faults": faults,
        "window_route_gap": wgap,
        "route_gap": gaps(served["before"], lower),
        "exact_route_gap": gaps(served["after"], False),
        "diameter_gap": reference.rel_gap(served["diameter"]["diameter"],
                                          reference.cc_diameter(ref), False),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the builder, never passed by a check
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--n0", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--control", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.n0 is not None and not args.rehearse:
        ap.error("--n0 is for --rehearse only")
    try:
        res = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), require_chip=not args.rehearse,
                       control=args.control, n0=args.n0)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print(f"generator lag: {json.dumps(res.pop('lag'))}", file=sys.stderr)
    print(f"window percentiles: {json.dumps(res.pop('spread'))}",
          file=sys.stderr)
    for k, v in res.pop("program_checks", {}).items():
        print(f"program check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    if args.control:
        print(f"the checks below are the control's ({args.control} reference "
              "in the program's place)", file=sys.stderr)
    for k, v in res["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    if args.rehearse:
        print(f"rehearsal on {res['device']['platform']}, not a result: "
              f"correct={res['correct']} attempted={res['attempted']} "
              f"failed={res['failed']} read {sorted(res['metrics'])}",
              file=sys.stderr)
        return 1
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
