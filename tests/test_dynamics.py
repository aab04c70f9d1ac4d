"""dynamics: incremental APSP parity, engine determinism, scenarios."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.diameter import (INF, adjacency_from_edges,
                                 adjacency_from_rings, is_edge, ring_edges)
from repro.core.topology import make_latency
from repro.dynamics import (ChurnEngine, DGROPolicy, Event, IncrementalDistances,
                            POLICIES, SCENARIOS, Trace)
from repro.dynamics import incremental as incr
from repro.membership.elastic import plan_rescale_from_engine


def _scipy_dists(adj: np.ndarray) -> np.ndarray:
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra

    a = np.asarray(adj, np.float64)
    return dijkstra(csr_matrix(np.where(is_edge(a), a, 0.0)), directed=False)


def _fresh_state(n_live: int, capacity: int, seed: int, dist="uniform"):
    w = make_latency(dist, capacity, seed=seed)
    rng = np.random.default_rng(seed)
    alive = np.zeros(capacity, bool)
    alive[:n_live] = True
    adj = adjacency_from_edges(w, ring_edges(rng.permutation(n_live)))
    return w, adj, alive


def _random_ops(inc: IncrementalDistances, rng, n_ops: int):
    """Yield a random churn op applied to ``inc``, one at a time."""
    for _ in range(n_ops):
        r = rng.random()
        live = inc.live_ids()
        if r < 0.55 or inc.n_live < 6:
            u, v = rng.choice(live, size=2, replace=False)
            inc.add_edge(int(u), int(v))
        elif r < 0.8 and (~inc.alive).any():
            u = int(np.flatnonzero(~inc.alive)[0])
            nbrs = rng.choice(live, size=min(3, len(live)), replace=False)
            inc.join(u, [int(x) for x in nbrs])
        else:
            inc.leave(int(rng.choice(live)))
        yield


def _assert_live_parity(inc: IncrementalDistances, tag=""):
    live = inc.live_ids()
    want = _scipy_dists(inc.adj[np.ix_(live, live)])
    got = np.asarray(inc.live_distances(), np.float64)
    reach = np.isfinite(want)
    assert (got < float(INF) / 2).tolist() == reach.tolist(), tag
    assert np.allclose(got[reach], want[reach], rtol=1e-4, atol=1e-3), tag


# ---------------------------------------------------------------------------
# incremental maintenance
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(10, 22), st.integers(0, 10_000))
def test_incremental_exact_after_every_event(n, seed):
    """Acceptance criterion: with every deletion triggering the tombstone
    rebuild (threshold=1), the maintained distances match a from-scratch
    Dijkstra rebuild after EVERY event of a random churn trace."""
    w, adj, alive = _fresh_state(n, n + 4, seed)
    inc = IncrementalDistances(w, adj, alive, rebuild_threshold=1)
    rng = np.random.default_rng(seed + 1)
    _assert_live_parity(inc, "init")
    for i, _ in enumerate(_random_ops(inc, rng, 30)):
        _assert_live_parity(inc, f"op{i}")
    assert inc.stats["rebuilds"] >= 1          # the tombstone path ran


def test_stale_distances_are_lower_bounds_until_refresh():
    """With a large rebuild threshold, post-leave distances may be stale but
    only ever UNDER-estimate (paths through tombstoned nodes); refresh()
    restores exactness."""
    w, adj, alive = _fresh_state(16, 16, seed=3)
    inc = IncrementalDistances(w, adj, alive, rebuild_threshold=100)
    rng = np.random.default_rng(4)
    for u in rng.choice(16, size=4, replace=False):
        inc.leave(int(u))
    assert inc.pending_deletions == 4 and inc.stats["rebuilds"] == 0
    live = inc.live_ids()
    want = _scipy_dists(inc.adj[np.ix_(live, live)])
    got = np.asarray(inc.live_distances(), np.float64)
    reach = np.isfinite(want)
    assert (got[reach] <= want[reach] + 1e-3).all()
    inc.refresh()
    assert inc.pending_deletions == 0
    _assert_live_parity(inc, "post-refresh")


def test_set_latency_increase_against_current_edge_weight():
    """A latency increase must be judged against the CURRENT edge weight
    (add_edge may have set it below w); otherwise the update is misread as
    a decrease and distances go permanently stale."""
    w, adj, alive = _fresh_state(10, 10, seed=1)
    inc = IncrementalDistances(w, adj, alive, rebuild_threshold=1)
    u, v = int(inc.live_ids()[0]), int(inc.live_ids()[5])
    inc.add_edge(u, v, weight=0.5)
    _assert_live_parity(inc, "after cheap edge")
    mid = 0.5 + float(w[u, v] - 0.5) / 2     # above 0.5, below w[u, v]
    inc.set_latency(u, v, mid)               # an INCREASE of the edge weight
    inc.refresh()
    _assert_live_parity(inc, "after increase + refresh")
    inc.set_latency(u, v, 0.25)              # and a genuine decrease relaxes
    _assert_live_parity(inc, "after decrease")


def test_full_mode_and_incremental_agree():
    w, adj, alive = _fresh_state(14, 18, seed=9)
    a = IncrementalDistances(w, adj, alive, mode="incremental",
                             rebuild_threshold=3)
    b = IncrementalDistances(w, adj, alive, mode="full")
    rng_a, rng_b = (np.random.default_rng(11) for _ in range(2))
    list(_random_ops(a, rng_a, 25))
    list(_random_ops(b, rng_b, 25))
    a.refresh()
    assert np.array_equal(a.alive, b.alive)
    assert np.allclose(a.live_distances(), b.live_distances(),
                       rtol=1e-4, atol=1e-3)


def test_batched_relax_matches_sequential():
    """(B,) replicas advanced in one device call == per-replica loop."""
    import jax.numpy as jnp

    b, n = 5, 12
    w = make_latency("gaussian", n, seed=0)
    rng = np.random.default_rng(2)
    dists, us, vs = [], [], []
    for i in range(b):
        ring = rng.permutation(n)
        adj = adjacency_from_rings(w, [ring])
        dists.append(_scipy_dists(adj))
        u, v = rng.choice(n, size=2, replace=False)
        us.append(int(u)), vs.append(int(v))
    dists = np.where(np.isfinite(dists), dists, float(INF)).astype(np.float32)
    ws = w[us, vs].astype(np.float32)
    got = incr.relax_edges_batched(jnp.asarray(dists), jnp.asarray(us),
                                   jnp.asarray(vs), jnp.asarray(ws))
    for i in range(b):
        want = incr.relax_edge(jnp.asarray(dists[i]), us[i], vs[i], ws[i])
        assert np.allclose(got[i], want, rtol=1e-5), i
    # the scanned stream applies T steps in one call
    t_steps = 3
    us_t = np.stack([np.roll(us, k) for k in range(t_steps)])
    vs_t = np.stack([np.roll(vs, k) for k in range(t_steps)])
    ws_t = w[us_t, vs_t].astype(np.float32)
    stream = incr.relax_edge_stream_batched(
        jnp.asarray(dists), jnp.asarray(us_t), jnp.asarray(vs_t),
        jnp.asarray(ws_t))
    ref = jnp.asarray(dists)
    for k in range(t_steps):
        ref = incr.relax_edges_batched(ref, jnp.asarray(us_t[k]),
                                       jnp.asarray(vs_t[k]),
                                       jnp.asarray(ws_t[k]))
    assert np.allclose(stream, ref, rtol=1e-5)


# ---------------------------------------------------------------------------
# rebuild over the live slots only
# ---------------------------------------------------------------------------

def _sparse_state(n_live: int, capacity: int, seed: int):
    """Live slots scattered over the capacity (a random alive mask), joined
    by three random rings over them."""
    w = make_latency("uniform", capacity, seed=seed)
    rng = np.random.default_rng(seed)
    live = np.sort(rng.choice(capacity, size=n_live, replace=False))
    alive = np.zeros(capacity, bool)
    alive[live] = True
    edges = np.concatenate([ring_edges(rng.permutation(live))
                            for _ in range(3)]) if n_live > 1 else []
    return w, adjacency_from_edges(w, edges), alive


def _rebuild_counters():
    from repro.obs import REGISTRY
    return (REGISTRY.get("repro_rebuild_slots_total").value,
            REGISTRY.get("repro_rebuild_updates_total").value)


# (capacity, n_live, method, slots): random masks, n_live at a bucket's
# edge (256, 257), one live node, every slot live (the gather is the
# identity)
_REBUILD_CASES = [(640, 241, "fw", 256), (1024, 256, "fw", 256),
                  (1024, 257, "fw", 512), (1024, 1, "fw", 256),
                  (384, 384, "fw", 384), (640, 241, "tiled", 256),
                  (1024, 257, "tiled", 512)]


@pytest.mark.parametrize("capacity,n_live,method,slots", _REBUILD_CASES)
def test_live_slot_rebuild_equals_full_capacity_rebuild(capacity, n_live,
                                                        method, slots):
    """The rebuild over the live slots gives the Floyd–Warshall of the whole
    (capacity, capacity) adjacency: bit for bit under ``fw``, within 1e-6
    relative under the blocked ``tiled`` method, whose 256-blocks group
    the additions differently."""
    from repro.core import batcheval
    w, adj, alive = _sparse_state(n_live, capacity, seed=capacity + n_live)
    with batcheval.eval_options(method=method):
        inc = IncrementalDistances(w, adj, alive)
        want = batcheval.apsp_matrices(inc.adj[None])[0]
    got = np.asarray(inc.distances)
    assert incr._rebuild_slots(inc.n_live, capacity) == slots
    if method == "fw":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("capacity,n_live", [(640, 241), (1024, 1)])
def test_live_slot_rebuild_leaves_dead_slots_isolated(capacity, n_live):
    w, adj, alive = _sparse_state(n_live, capacity, seed=7)
    inc = IncrementalDistances(w, adj, alive)
    inc.leave(int(inc.live_ids()[0]))
    inc.rebuild()
    d = np.asarray(inc.distances)
    dead = np.flatnonzero(~inc.alive)
    expect = np.full((dead.size, capacity), INF, np.float32)
    expect[np.arange(dead.size), dead] = 0.0
    np.testing.assert_array_equal(d[dead], expect)
    np.testing.assert_array_equal(d[:, dead], expect.T)


@pytest.mark.parametrize("capacity,n_live,slots", [(640, 241, 256),
                                                   (640, 300, 512),
                                                   (384, 384, 384)])
def test_rebuild_counts_its_slots_and_updates(capacity, n_live, slots):
    w, adj, alive = _sparse_state(n_live, capacity, seed=3)
    inc = IncrementalDistances(w, adj, alive)
    s0, u0 = _rebuild_counters()
    inc.rebuild()
    inc.rebuild()
    assert _rebuild_counters() == (s0 + 2 * slots, u0 + 2 * slots ** 3)


def test_rebuild_in_a_new_bucket_is_timed_as_a_compile():
    """Construction compiles the bucket that holds n_live (512) and the one
    above (the capacity, 704); a rebuild in a third bucket (256) lands in
    the compile histogram, and the next one there in the execute one."""
    from repro.obs import REGISTRY
    w, adj, alive = _sparse_state(300, 704, seed=8)
    inc = IncrementalDistances(w, adj, alive, rebuild_threshold=1000)
    for node in inc.live_ids()[:50]:
        inc.leave(int(node))
    assert incr._rebuild_slots(inc.n_live, inc.capacity) == 256
    hists = [REGISTRY.get(f"repro_jit_{phase}_seconds").labels(
        fn="incremental.rebuild") for phase in ("compile", "execute")]
    counts = [h.count for h in hists]
    inc.rebuild()
    assert [h.count for h in hists] == [counts[0] + 1, counts[1]]
    inc.rebuild()
    assert [h.count for h in hists] == [counts[0] + 1, counts[1] + 1]
    _assert_live_parity(inc, "after a rebuild in a new bucket")


def test_churn_across_a_bucket_edge_traces_no_new_program():
    """Construction runs the bucket that holds n_live and compiles the one
    above it, so churn across their edge (256 <-> 257) traces nothing."""
    from repro.core import batcheval
    w, adj, alive = _sparse_state(256, 768, seed=4)
    inc = IncrementalDistances(w, adj, alive, rebuild_threshold=100)
    live = inc.live_ids()
    inc.join(int(np.flatnonzero(~inc.alive)[0]), [int(live[0])])
    inc.leave(int(live[1]))               # the relax/join/tombstone programs
    programs = (incr._gather_live, incr._scatter_live, batcheval.batched_apsp)
    before = [f._cache_size() for f in programs]
    sizes = []
    for step in range(4):
        if step % 2:
            inc.leave(int(inc.live_ids()[-1]))
        else:
            inc.join(int(np.flatnonzero(~inc.alive)[0]),
                     [int(x) for x in inc.live_ids()[:2]])
        inc.rebuild()
        sizes.append(incr._rebuild_slots(inc.n_live, inc.capacity))
    assert sizes == [512, 256, 512, 256]
    assert [f._cache_size() for f in programs] == before
    _assert_live_parity(inc, "after edge churn")


# ---------------------------------------------------------------------------
# traces + scenarios
# ---------------------------------------------------------------------------

def test_trace_json_roundtrip_and_determinism():
    for name, make in SCENARIOS.items():
        t1, t2 = make(n0=20, seed=5), make(n0=20, seed=5)
        assert t1.events == t2.events, name          # generator determinism
        rt = Trace.from_json(t1.to_json())
        assert rt.events == t1.events and rt.n0 == t1.n0
        assert (rt.capacity, rt.dist, rt.seed) == (
            t1.capacity, t1.dist, t1.seed)
        from repro.dynamics.scenarios import EVENT_KINDS
        assert all(e.kind in EVENT_KINDS for e in t1.events), name


def test_event_kind_validated():
    with pytest.raises(ValueError):
        Event(time=0.0, kind="reboot")


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_engine_survives_full_drain_then_rejoin():
    """A trace may empty the fleet entirely; the next joiner re-seeds the
    rings instead of crashing the splice."""
    events = [Event(time=1.0, kind="leave", node=0),
              Event(time=2.0, kind="leave", node=1),
              Event(time=3.0, kind="join", node=2),
              Event(time=4.0, kind="join", node=3)]
    trace = Trace(n0=2, capacity=4, dist="uniform", seed=0,
                  events=events, name="drain")
    for pname, P in POLICIES.items():
        eng = ChurnEngine(trace, P(), seed=0)
        eng.run()
        assert eng.inc.n_live == 2, pname


def test_engine_run_is_single_use():
    trace = SCENARIOS["flash_crowd"](n0=12, seed=0)
    eng = ChurnEngine(trace, POLICIES["rapid"](), seed=0)
    eng.run()
    with pytest.raises(RuntimeError):
        eng.run()


def test_engine_deterministic_replay():
    trace = SCENARIOS["poisson_churn"](n0=18, seed=2)
    runs = [ChurnEngine(trace, DGROPolicy(), seed=7,
                        detect_failures=True).run() for _ in range(2)]
    assert runs[0].samples == runs[1].samples
    assert runs[0].final_diameter == runs[1].final_diameter


@pytest.mark.parametrize("policy", list(POLICIES))
def test_engine_scenarios_stay_connected(policy):
    for name in ("flash_crowd", "regional_failure", "straggler_storm"):
        trace = SCENARIOS[name](n0=18, seed=1)
        res = ChurnEngine(trace, POLICIES[policy](), seed=3,
                          detect_failures=True).run()
        assert np.isfinite(res.final_diameter), (name, policy)
        assert res.final_diameter < float(INF) / 2, (name, policy)
        assert all(s.diameter < float(INF) / 2 for s in res.samples), name


def test_engine_distances_exact_after_trace():
    """End-to-end acceptance: replaying a scenario through the engine, the
    incrementally-maintained diameter equals a from-scratch rebuild."""
    from repro.core.diameter import diameter_scipy

    trace = SCENARIOS["poisson_churn"](n0=16, seed=6)
    eng = ChurnEngine(trace, POLICIES["rapid"](), seed=1)
    res = eng.run()
    live = eng.live_ids()
    want = diameter_scipy(eng.inc.adj[np.ix_(live, live)])
    assert res.final_diameter == pytest.approx(want, rel=1e-4)


def test_regional_failure_kills_site_and_dgro_recovers():
    trace = SCENARIOS["regional_failure"](n0=34, seed=4)
    victims = {e.node for e in trace.events}
    eng = ChurnEngine(trace, DGROPolicy(adapt_every=1), seed=2,
                      detect_failures=True)
    res = eng.run()
    assert not eng.alive[list(victims)].any()
    assert eng.inc.n_live == 34 - len(victims)
    assert np.isfinite(res.final_diameter)


def test_plan_rescale_from_engine_excludes_dead_and_stragglers():
    events = [Event(time=1_000.0, kind="fail", node=5),
              Event(time=3_000.0, kind="straggler", node=11, factor=25.0)]
    trace = Trace(n0=24, capacity=24, dist="fabric", seed=3,
                  events=events, name="rescale")
    eng = ChurnEngine(trace, DGROPolicy(), seed=0, detect_failures=True)
    eng.run()
    plan = plan_rescale_from_engine(eng, model_hosts=2, old_world=24)
    assert 5 not in plan.hosts and 11 not in plan.hosts
    pods, data, model = plan.mesh_shape
    assert pods * data * model == len(plan.hosts) and model == 2


def test_dgro_self_repair_survives_shrinking_below_sample_size():
    """Regression: a network churning down to a handful of nodes used to
    crash DGRO's Algorithm-3 self-repair inside measure_latency_stats
    (global sample of k > n-1 without replacement).  A pure-leave trace
    shrinking 12 -> 4 with adapt_every=1 must replay to completion."""
    events = [Event(time=1_000.0 * (i + 1), kind="leave", node=i)
              for i in range(8)]
    trace = Trace(n0=12, capacity=12, dist="uniform", seed=5,
                  events=events, name="shrink")
    eng = ChurnEngine(trace, DGROPolicy(adapt_every=1), seed=1)
    res = eng.run()
    assert eng.inc.n_live == 4
    assert np.isfinite(res.final_diameter)


# ---------------------------------------------------------------------------
# input validation (satellite)
# ---------------------------------------------------------------------------

def test_adjacency_from_rings_rejects_non_permutations():
    from repro.core.diameter import adjacency_from_edges

    w = make_latency("uniform", 8, seed=0)
    with pytest.raises(ValueError):
        adjacency_from_rings(w, [np.array([0, 1, 2])])          # too short
    with pytest.raises(ValueError):
        adjacency_from_rings(w, [np.array([0, 1, 2, 3, 4, 5, 6, 6])])  # dup
    with pytest.raises(ValueError):
        adjacency_from_edges(w, [(0, 9)])                       # out of range
    with pytest.raises(ValueError):
        adjacency_from_edges(w, [(-1, 2)])
    # valid inputs still pass
    adjacency_from_rings(w, [np.arange(8)])
    adjacency_from_edges(w, [(0, 7)])


# ---------------------------------------------------------------------------
# instrumentation: host copies of D, the rebuild's phases, engine.policy
# ---------------------------------------------------------------------------

def _span_count(name: str) -> int:
    from repro.obs import REGISTRY
    return REGISTRY.get("repro_span_seconds").labels(span=name).count


def _host_copies():
    from repro.obs import REGISTRY
    return (REGISTRY.get("repro_dist_host_copies_total").value,
            REGISTRY.get("repro_dist_host_bytes_total").value,
            _span_count("dist.to_host"))


def test_host_copies_of_distances_counted_once_per_mutation():
    from repro.obs import REGISTRY, parse_prometheus
    c = 24
    w, adj, alive = _fresh_state(16, c, seed=5)
    inc = IncrementalDistances(w, adj, alive)
    n0, b0, s0 = _host_copies()
    inc.distances
    inc.live_distances()                   # a second read: no copy
    assert _host_copies() == (n0 + 1, b0 + c * c * 4, s0 + 1)
    inc.join(16, [0, 1])                   # a mutation: the next read copies
    inc.distances
    inc.distances
    assert _host_copies() == (n0 + 2, b0 + 2 * c * c * 4, s0 + 2)
    # the relax/join/tombstone updates only enqueue: they carry no timing
    fns = {dict(k).get("fn") for k in parse_prometheus(
        REGISTRY.render_prometheus()).get("repro_jit_execute_seconds_count",
                                          {})}
    assert not fns & {"incremental.relax", "incremental.join",
                      "incremental.tombstone"}


def test_rebuild_phases_in_spans_and_scoring_outside_them():
    from repro.core import batcheval
    from repro.obs import REGISTRY
    w, adj, alive = _fresh_state(16, 24, seed=6)
    inc = IncrementalDistances(w, adj, alive)
    phases = ("rebuild.to_device", "rebuild.device", "rebuild.to_host")
    hist = REGISTRY.get("repro_span_seconds")
    before = {p: (hist.labels(span=p).count, hist.labels(span=p).sum)
              for p in phases}
    jit = REGISTRY.get("repro_jit_execute_seconds").labels(
        fn="incremental.rebuild")
    jit0 = (jit.count, jit.sum)
    inc.rebuild()
    grew = {p: (hist.labels(span=p).count - before[p][0],
                hist.labels(span=p).sum - before[p][1]) for p in phases}
    # the adjacency's upload and one device phase; the result stays on the
    # device, so nothing is downloaded
    assert [grew[p][0] for p in phases] == [1, 1, 0]
    assert jit.count == jit0[0] + 1
    assert sum(g[1] for g in grew.values()) <= jit.sum - jit0[1]
    counts = [hist.labels(span=p).count for p in phases]
    batcheval.diameters(adj[None])         # re-optimizer scoring path
    batcheval.apsp_matrices(adj[None])
    assert [hist.labels(span=p).count for p in phases] == counts


def test_engine_policy_span_once_per_applied_event():
    trace = SCENARIOS["poisson_churn"](n0=18, seed=1)
    events, n_fail = [], 0
    for i, e in enumerate(sorted(trace.events, key=lambda e: e.time)):
        if e.kind == "leave" and i % 3 == 0:      # some crash instead
            e = Event(time=e.time, kind="fail", node=e.node)
            n_fail += 1
        events.append(e)
    assert n_fail and {e.kind for e in events} == {"join", "leave", "fail"}
    eng = ChurnEngine(Trace(n0=trace.n0, capacity=trace.capacity,
                            dist=trace.dist, seed=trace.seed, events=[],
                            name="policy-span"),
                      DGROPolicy(), seed=0, detect_failures=True)
    before = _span_count("engine.policy")
    applied = sum(eng.process(e) for e in events) + eng.flush()
    # each fail is applied twice: the crash, then its SWIM-confirmed leave
    assert applied == len(events) + n_fail
    assert _span_count("engine.policy") - before == applied
