"""Span timers and JIT-aware timing over the global metrics registry.

Three tools, all ``time.perf_counter``-based and thread-safe:

* :func:`span` — nestable wall-time spans recorded into the
  ``repro_span_seconds{span=...}`` histogram, with a thread-local stack so
  nested spans know their parent (``current_span()``); the service state's
  entry points, the re-optimizer's phases, the host copies of the distance
  matrix, the rebuild's phases and the churn engine's per-event host work
  use these.

* :func:`jit_span` — the JIT-aware variant for jit'd entry points
  (``batcheval.diameters``, ``rollout.rollout_episodes``, the incremental
  relax/join/rebuild updates).  jax compiles on first call per
  (function, static-shape) combination, so a naive histogram mixes
  multi-second compiles into the microsecond steady state.  ``jit_span``
  keys each timing by ``(name, key)`` — pass the shape/static-arg tuple as
  ``key`` — and routes the FIRST observation per key into
  ``repro_jit_compile_seconds{fn=...}`` and every later one into
  ``repro_jit_execute_seconds{fn=...}``.

* :class:`TimedRLock` — an RLock whose *acquisition wait* is observed into
  a histogram (re-entrant acquisitions are not recorded: the owner never
  waits), and optionally its *hold* time, labelled by the span that took
  it.  ``ServiceState.lock`` is one of these, so lock contention between
  the HTTP handler threads and the re-optimizer is measurable, and so is
  who holds the lock while others wait.

All three also mark their blocks on the profiler's clock: a span or
``jit_span`` named ``x`` opens ``jax.profiler.TraceAnnotation("repro.x")``
and a lock wait opens ``repro.lock.wait`` (:func:`annotate`), so the
spans of one request nest on its thread's line in the same trace as the
device's operations.  This module never imports jax itself: the
annotation resolves on first use, once something else has loaded jax.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Dict, Optional, Tuple

from .metrics import LATENCY_BUCKETS_S, REGISTRY, Histogram, MetricsRegistry

__all__ = ["span", "current_span", "jit_span", "jit_phase",
           "reset_jit_state", "TimedRLock", "annotate"]

_local = threading.local()

# spans can be long (an exact-diameter refresh, a DQN reopt): stretch the
# default bucket range upward
SPAN_BUCKETS_S: Tuple[float, ...] = (
    .0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


def _span_histogram(registry: MetricsRegistry) -> Histogram:
    return registry.histogram(
        "repro_span_seconds", "wall time per named span",
        labels=("span",), buckets=SPAN_BUCKETS_S)


# instruments on the default registry are resolved ONCE at import — span()
# and jit_span() sit on ingest/route hot paths, and re-resolving through the
# registry lock per call is measurable (the fig18 overhead gate)
_DEFAULT_SPAN = _span_histogram(REGISTRY)


def _jit_instruments(registry: MetricsRegistry):
    return (registry.histogram(
                "repro_jit_compile_seconds",
                "first-call (traced+compiled) time per jit entry point",
                labels=("fn",), buckets=SPAN_BUCKETS_S),
            registry.histogram(
                "repro_jit_execute_seconds",
                "steady-state execute time per jit entry point",
                labels=("fn",), buckets=SPAN_BUCKETS_S))


_DEFAULT_JIT = _jit_instruments(REGISTRY)


# ---------------------------------------------------------------------------
# the profiler's clock
# ---------------------------------------------------------------------------

_NO_ANNOTATION = contextlib.nullcontext()
_annotation_cls = None


def annotate(name: str):
    """``jax.profiler.TraceAnnotation(name)``: mark a block on the
    profiler's clock, where the device's operations are, without timing
    it.  A no-op until jax is loaded; importing ``repro.obs`` never loads
    it (a load generator or scraper stays free of jax)."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return _NO_ANNOTATION
        from jax.profiler import TraceAnnotation
        cls = _annotation_cls = TraceAnnotation
    return cls(name)


def _stack() -> list:
    st = getattr(_local, "spans", None)
    if st is None:
        st = _local.spans = []
    return st


def current_span() -> Optional[str]:
    """Name of the innermost active span on this thread, or None."""
    st = _stack()
    return st[-1] if st else None


# labelled-child handles (and the annotation name) resolved once per
# span/fn name on the default registry — plain dict reads, no locks, on the
# hot path (racy writes are harmless: labels() dedupes children under the
# instrument lock)
_span_children: dict = {}
_jit_children: dict = {}


class span:
    """``with span("reopt.capture"): ...`` — record the block's wall time.

    Nesting is explicit: each span records its own duration under its own
    name (inclusive of children), and ``current_span()`` exposes the
    innermost name while inside the block.  The block is also annotated
    ``repro.<name>`` on the profiler's clock.  A class-based context
    manager (not ``@contextmanager``): span sits on ingest/route hot paths
    and the generator protocol alone costs more than the two clock reads.
    """

    __slots__ = ("_name", "_registry", "_hist", "_ann", "_t0", "_on")

    def __init__(self, name: str, *, registry: MetricsRegistry = REGISTRY):
        self._name = name
        self._registry = registry

    def __enter__(self) -> "span":
        reg = self._registry
        if not reg.enabled:          # disabled: no clock reads, no lookups
            self._on = False
            return self
        self._on = True
        if reg is REGISTRY:
            pair = _span_children.get(self._name)
            if pair is None:
                pair = (_DEFAULT_SPAN.labels(span=self._name),
                        "repro." + self._name)
                _span_children[self._name] = pair
        else:
            pair = (_span_histogram(reg).labels(span=self._name),
                    "repro." + self._name)
        self._hist = pair[0]
        self._ann = annotate(pair[1])
        self._ann.__enter__()
        _stack().append(self._name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._on:
            dt = time.perf_counter() - self._t0
            _stack().pop()
            self._ann.__exit__(None, None, None)
            self._hist.observe(dt)
        return False


# ---------------------------------------------------------------------------
# JIT-aware timing
# ---------------------------------------------------------------------------

_jit_lock = threading.Lock()
_jit_seen: set = set()


def reset_jit_state() -> None:
    """Forget which (name, key) combinations have been seen (tests)."""
    with _jit_lock:
        _jit_seen.clear()


def _is_first(name: str, key) -> bool:
    k = (name, key)
    if k in _jit_seen:               # lock-free steady state (atomic read)
        return False
    with _jit_lock:
        if k in _jit_seen:
            return False
        _jit_seen.add(k)
        return True


def jit_phase(name: str, key=None) -> str:
    """Compile/execute split for callers that time a jit'd call themselves.

    Returns ``"compile"`` on the first call per (name, key) and
    ``"execute"`` after — the same split ``jit_span`` applies, exposed as
    a label value for code that observes its own histogram (e.g. the
    ``repro_apsp_seconds{method, phase}`` engine timings in
    ``core.batcheval``).  Shares ``reset_jit_state()`` with ``jit_span``.
    """
    return "compile" if _is_first(name, key) else "execute"


class jit_span:
    """Time a jit'd call, separating first-call compile from steady state.

    ``key`` should capture whatever triggers retracing (shapes, static
    args); the first observation per (name, key) lands in
    ``repro_jit_compile_seconds``, the rest in
    ``repro_jit_execute_seconds``.  The block is annotated ``repro.<name>``
    on the profiler's clock.
    """

    __slots__ = ("_name", "_key", "_registry", "_hist", "_ann", "_t0", "_on")

    def __init__(self, name: str, key=None, *,
                 registry: MetricsRegistry = REGISTRY):
        self._name = name
        self._key = key
        self._registry = registry

    def __enter__(self) -> "jit_span":
        reg = self._registry
        if not reg.enabled:          # disabled: don't even consume "first"
            self._on = False
            return self
        self._on = True
        first = _is_first(self._name, self._key)
        if reg is REGISTRY:
            ck = (first, self._name)
            h = _jit_children.get(ck)
            if h is None:
                h = _DEFAULT_JIT[0 if first else 1].labels(fn=self._name)
                _jit_children[ck] = h
        else:
            compile_h, execute_h = _jit_instruments(reg)
            h = (compile_h if first else execute_h).labels(fn=self._name)
        self._hist = h
        self._ann = annotate("repro." + self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._on:
            dt = time.perf_counter() - self._t0
            self._ann.__exit__(None, None, None)
            self._hist.observe(dt)
        return False


# ---------------------------------------------------------------------------
# lock wait and hold
# ---------------------------------------------------------------------------

class TimedRLock:
    """Drop-in re-entrant lock recording acquisition *wait* time, and, when
    given a ``hold`` histogram labelled ``holder``, the *hold* time.

    Only top-level acquisitions are observed — a re-entrant acquire by the
    owning thread never blocks, and recording it would drown the histogram
    in zeros.  The holder is the innermost span open on the acquiring
    thread (``current_span()``), or ``"other"``: span names are a closed
    set in code, so the label stays bounded.  API-compatible with
    ``threading.RLock`` for ``with``-block and ``acquire``/``release`` use.
    """

    def __init__(self, histogram: Optional[Histogram] = None, *,
                 registry: MetricsRegistry = REGISTRY,
                 name: str = "repro_lock_wait_seconds",
                 help: str = "time spent waiting to acquire a shared lock",
                 hold: Optional[Histogram] = None):
        self._lock = threading.RLock()
        self._hist = histogram if histogram is not None else \
            registry.histogram(name, help, buckets=LATENCY_BUCKETS_S)
        self._hold = hold
        self._hold_children: Dict[str, Histogram] = {}
        self._owner: Optional[int] = None
        self._depth = 0
        self._holder = "other"
        self._t_held = 0.0

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        me = threading.get_ident()
        if self._owner == me:                  # re-entrant: no wait
            ok = self._lock.acquire(blocking, timeout)
            if ok:
                self._depth += 1
            return ok
        t0 = time.perf_counter()
        with annotate("repro.lock.wait"):
            ok = self._lock.acquire(blocking, timeout)
        if ok:
            self._t_held = time.perf_counter()
            self._hist.observe(self._t_held - t0)
            self._owner = me
            self._depth = 1
            self._holder = current_span() or "other"
        return ok

    def release(self) -> None:
        if self._owner != threading.get_ident():
            raise RuntimeError("cannot release un-acquired TimedRLock")
        self._depth -= 1
        if self._depth == 0:
            self._owner = None
            if self._hold is not None:
                self._observe_hold(time.perf_counter() - self._t_held)
        self._lock.release()

    def _observe_hold(self, seconds: float) -> None:
        h = self._hold_children.get(self._holder)
        if h is None:
            h = self._hold_children[self._holder] = \
                self._hold.labels(holder=self._holder)
        h.observe(seconds)

    def __enter__(self) -> "TimedRLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()
