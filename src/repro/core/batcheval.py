"""Batched topology evaluation — the bulk diameter/APSP engine.

Everything DGRO measures (GA populations, candidate ring selection,
partitioned construction, design-space sweeps, service re-optimization)
reduces to "score many candidate overlays by diameter".  This module turns
that into memory-bounded device calls that scale to N=4096+ with batches in
the hundreds.

Layout of the module:

* graph assembly — ``rings_to_edges`` / ``adjacency_batch_from_edges`` /
  ``adjacency_batch_from_rings`` build (B, N, N) tensors with vectorized
  numpy scatters; ``overlay_with_rings`` fuses a base overlay with B
  candidate rings; ``pad_adjacency_blocks`` pads variable-size blocks into
  one batch; :class:`RingBlockSource` is the LAZY equivalent — it hands the
  streaming facade one chunk of dense matrices at a time, so a 100k-genome
  GA budget never materializes a (B, N, N) host tensor either.
* device compute — ``batched_apsp`` / ``batched_diameter`` are jit'd per
  chunk.  Three interchangeable methods (cross-validated in tests):
  ``"fw"`` (vectorized Floyd-Warshall, the CPU speed path), ``"squaring"``
  (min-plus squaring; batched Pallas kernel on TPU), and ``"tiled"``
  (blocked Floyd-Warshall over a (N/T, N/T) block grid —
  ``kernels.minplus.apsp_tiled`` — whose working set is panels, not cubes;
  the TPU default past ``REPRO_APSP_TILED_N`` nodes).
* host facade — ``diameters`` / ``apsp_matrices`` / ``diameters_of_rings``
  STREAM the batch through fixed-size chunks (``default_chunk`` sizes them
  from a per-method memory model, ``REPRO_APSP_MEM_BYTES`` overrides the
  budget): peak device footprint is one chunk, never the whole batch.
  Optional reduced-precision evaluation (``dtype="bfloat16"`` or
  ``"int16"``-quantized latencies) measures its own error on float32
  probes and falls back to an exact rerun past ``exact_rtol``.
  ``eval_options`` scopes any of these knobs over a call tree.
* sharded compute — ``diameters_sharded`` shards the batch axis over a
  device mesh (``launch.mesh.make_eval_mesh``); ``apsp_rowshard`` shards
  the ROW-BLOCK axis of one huge matrix (min-plus squaring with an
  all-gather per squaring, following the ``parallel_ring_shmap`` pattern).

Instrumentation: every engine call lands in the pre-registered
``repro_apsp_seconds{method, phase}`` histogram (compile/execute split via
``obs.jit_phase``) and updates the ``repro_apsp_workingset_bytes`` gauge
with the modeled per-call device footprint; quantized evals record their
measured error and ``repro_apsp_exact_fallbacks_total``.
``last_eval_report()`` returns the same facts programmatically.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import REGISTRY, jit_phase, jit_span
from repro.obs.tracing import SPAN_BUCKETS_S

from .diameter import INF, is_edge, largest_cc_diameter

__all__ = [
    "rings_to_edges",
    "adjacency_batch_from_edges",
    "adjacency_batch_from_rings",
    "overlay_with_rings",
    "pad_adjacency_blocks",
    "RingBlockSource",
    "batched_apsp",
    "batched_diameter",
    "diameters",
    "diameters_of_rings",
    "diameters_sharded",
    "apsp_matrices",
    "apsp_rowshard",
    "quantize_latency",
    "eval_options",
    "last_eval_report",
    "default_chunk",
    "workingset_bytes",
]

METHODS = ("fw", "squaring", "tiled")
DTYPES = ("float32", "bfloat16", "int16")
DEFAULT_BUDGET_BYTES = 1 << 28          # ~256 MiB of device temporaries
DEFAULT_TILED_N = 512                   # TPU auto-switch to the tiled path
DEFAULT_EXACT_RTOL = 0.05               # quantized-eval fallback threshold

_APSP_SECONDS = REGISTRY.histogram(
    "repro_apsp_seconds",
    "device wall time per APSP/diameter engine call, compile/execute split",
    labels=("method", "phase"), buckets=SPAN_BUCKETS_S)
_APSP_WORKINGSET = REGISTRY.gauge(
    "repro_apsp_workingset_bytes",
    "modeled peak device working set of the last engine call")
_APSP_QUANT_ERR = REGISTRY.gauge(
    "repro_apsp_quant_rel_err",
    "measured relative diameter error of the last reduced-precision eval")
_APSP_FALLBACKS = REGISTRY.counter(
    "repro_apsp_exact_fallbacks_total",
    "reduced-precision evals that exceeded exact_rtol and re-ran in float32")


# ---------------------------------------------------------------------------
# graph assembly (host, vectorized)
# ---------------------------------------------------------------------------

def rings_to_edges(genomes) -> np.ndarray:
    """``(B, K, N)`` ring permutations -> ``(B, K*N, 2)`` edge lists.

    Accepts a (B, K, N) array, a (B, N) array (K=1), or a nested list of
    per-genome ring permutations.
    """
    g = np.asarray(genomes, dtype=np.intp)
    if g.ndim == 2:
        g = g[:, None, :]
    assert g.ndim == 3, g.shape
    nxt = np.roll(g, -1, axis=-1)
    return np.stack([g, nxt], axis=-1).reshape(g.shape[0], -1, 2)


def adjacency_batch_from_edges(w: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Batch of weighted adjacencies from per-candidate edge lists.

    ``edges`` is (B, E, 2); returns (B, N, N) float32 with INF on non-edges
    and 0 diagonal.  The scatter is one ``np.minimum.at`` over both edge
    directions, so duplicate/parallel edges resolve to the min weight
    exactly like the scalar loop in ``diameter.adjacency_from_edges``.
    """
    w = np.asarray(w)
    n = w.shape[0]
    e = np.asarray(edges, dtype=np.intp)
    assert e.ndim == 3 and e.shape[-1] == 2, e.shape
    b = e.shape[0]
    d = np.full((b, n, n), float(INF), dtype=np.float32)
    d[:, np.arange(n), np.arange(n)] = 0.0
    if e.shape[1]:
        bi = np.broadcast_to(np.arange(b)[:, None], e.shape[:2])
        u, v = e[..., 0], e[..., 1]
        np.minimum.at(d, (bi, u, v), w[u, v].astype(np.float32))
        np.minimum.at(d, (bi, v, u), w[v, u].astype(np.float32))
    return d


def adjacency_batch_from_rings(w: np.ndarray, genomes) -> np.ndarray:
    """(B, K, N) ring permutations -> (B, N, N) union-of-rings adjacencies."""
    return adjacency_batch_from_edges(w, rings_to_edges(genomes))


def overlay_with_rings(adj: np.ndarray, w: np.ndarray, rings) -> np.ndarray:
    """B candidate overlays: the base ``adj`` each augmented with one ring."""
    cand = adjacency_batch_from_rings(w, rings)
    return np.minimum(np.asarray(adj, np.float32)[None], cand)


def pad_adjacency_blocks(blocks: Sequence[np.ndarray]) -> np.ndarray:
    """Pad variable-size adjacencies to one (B, N_max, N_max) batch.

    Padded nodes are isolated (INF rows/cols, 0 diagonal): each is a
    singleton component, so the largest-CC diameter of the padded graph
    equals the block's own diameter whenever the block has >= 1 node.
    """
    blocks = [np.asarray(b, np.float32) for b in blocks]
    n_max = max(b.shape[0] for b in blocks)
    out = np.full((len(blocks), n_max, n_max), float(INF), dtype=np.float32)
    out[:, np.arange(n_max), np.arange(n_max)] = 0.0
    for i, b in enumerate(blocks):
        out[i, :b.shape[0], :b.shape[0]] = b
    return out


class RingBlockSource:
    """Lazy adjacency source: assembles (chunk, N, N) blocks on demand.

    The streaming facade accepts any object with ``__len__``, ``.n`` and
    ``.block(lo, hi)``; this one defers ``adjacency_batch_from_rings`` so
    ``diameters_of_rings`` holds at most ONE chunk of dense matrices on the
    host — at B=100k, N=4096 the eager tensor would be 6.7 TB.
    """

    def __init__(self, w: np.ndarray, genomes):
        self.w = np.asarray(w)
        g = np.asarray(genomes, dtype=np.intp)
        if g.ndim == 2:
            g = g[:, None, :]
        assert g.ndim == 3, g.shape
        self.genomes = g

    def __len__(self) -> int:
        return self.genomes.shape[0]

    @property
    def n(self) -> int:
        return self.w.shape[0]

    def block(self, lo: int, hi: int) -> np.ndarray:
        return adjacency_batch_from_rings(self.w, self.genomes[lo:hi])


class _ArraySource:
    """Adapter giving an eager (B, N, N) array the block-source protocol."""

    def __init__(self, adjs: np.ndarray):
        adjs = np.asarray(adjs, dtype=np.float32)
        assert adjs.ndim == 3 and adjs.shape[1] == adjs.shape[2], adjs.shape
        self.adjs = adjs

    def __len__(self) -> int:
        return self.adjs.shape[0]

    @property
    def n(self) -> int:
        return self.adjs.shape[-1]

    def block(self, lo: int, hi: int) -> np.ndarray:
        return self.adjs[lo:hi]


def _as_source(adjs):
    if hasattr(adjs, "block") and hasattr(adjs, "n"):
        return adjs
    return _ArraySource(adjs)


# ---------------------------------------------------------------------------
# scoped evaluation options
# ---------------------------------------------------------------------------

_OPT_KEYS = frozenset({"method", "dtype", "chunk", "tile", "use_kernel",
                       "budget_bytes", "exact_rtol"})
_OPT_ENV = {
    "method": "REPRO_APSP_METHOD",
    "dtype": "REPRO_APSP_DTYPE",
    "chunk": "REPRO_APSP_CHUNK",
    "tile": "REPRO_APSP_TILE",
    "budget_bytes": "REPRO_APSP_MEM_BYTES",
    "exact_rtol": "REPRO_APSP_RTOL",
}
_OPT_PARSE = {"chunk": int, "tile": int, "budget_bytes": int,
              "exact_rtol": float}

_ctx = threading.local()


@contextlib.contextmanager
def eval_options(**opts):
    """Scope engine knobs over a call tree without threading kwargs.

    ``with eval_options(dtype="bfloat16", method="tiled"): ...`` makes
    every facade call inside the block (including ones buried in
    ``selection.adapt`` or the service re-optimizer) pick up the options.
    Precedence: explicit call-site kwarg > innermost ``eval_options`` >
    ``REPRO_APSP_*`` env var > built-in default.  Keys: method, dtype,
    chunk, tile, use_kernel, budget_bytes, exact_rtol.
    """
    unknown = set(opts) - _OPT_KEYS
    if unknown:
        raise ValueError(f"unknown eval options {sorted(unknown)}; "
                         f"known: {sorted(_OPT_KEYS)}")
    if opts.get("method") is not None and opts["method"] not in METHODS:
        raise ValueError(f"unknown method {opts['method']!r}; "
                         f"options {METHODS}")
    if opts.get("dtype") is not None and opts["dtype"] not in DTYPES:
        raise ValueError(f"unknown dtype {opts['dtype']!r}; options {DTYPES}")
    stack = getattr(_ctx, "stack", None)
    if stack is None:
        stack = _ctx.stack = []
    stack.append(dict(opts))
    try:
        yield
    finally:
        stack.pop()


def _opt(name: str, explicit=None):
    """Resolve one option: explicit > context > env > None."""
    if explicit is not None:
        return explicit
    for frame in reversed(getattr(_ctx, "stack", []) or []):
        if frame.get(name) is not None:
            return frame[name]
    env = _OPT_ENV.get(name)
    if env and env in os.environ:
        return _OPT_PARSE.get(name, str)(os.environ[env])
    return None


_report = threading.local()


def last_eval_report() -> dict:
    """Facts about this thread's most recent facade call: resolved method /
    dtype / chunk / tile, modeled working-set bytes, device call count,
    measured quantization error and whether the exact fallback fired."""
    return dict(getattr(_report, "data", {}))


# ---------------------------------------------------------------------------
# device compute (jit, one call per chunk)
# ---------------------------------------------------------------------------

def _batched_minplus(a: jnp.ndarray, b: jnp.ndarray,
                     use_kernel: bool) -> jnp.ndarray:
    """One batched min-plus squaring step, via the kernels.minplus entry
    point — compiled Pallas grid-over-batch on TPU, vmapped jnp oracle on
    CPU — so the default TPU path actually runs the kernel.  ``use_kernel``
    forces the Pallas body (interpret mode off-TPU) for cross-validation."""
    from repro.kernels.minplus import ops as minplus_ops

    return minplus_ops.minplus_batched(a, b, force_kernel=use_kernel)


def _auto_method(use_kernel: bool, n: Optional[int] = None,
                 tiled_n: int = DEFAULT_TILED_N) -> str:
    """Backend- and size-aware default: TPU runs min-plus squaring (the
    batched Pallas kernel) until the tiled blocked-FW engine wins past
    ``tiled_n`` nodes; CPU runs vectorized FW, whose fused rank-1 update
    beats both the squaring oracle's (B, N, N, N) broadcast and the tiled
    fallback's per-block dispatch (measured in benchmarks/fig20_scale)."""
    if jax.default_backend() == "tpu":
        if n is not None and n >= tiled_n:
            return "tiled"
        return "squaring"
    return "squaring" if use_kernel else "fw"


def _resolve_method(use_kernel: bool, method: Optional[str],
                    n: Optional[int] = None) -> str:
    if method is not None:
        assert method in METHODS, method
        return method
    return _auto_method(use_kernel, n)


@functools.partial(jax.jit, static_argnames=("use_kernel", "method",
                                             "symmetric", "dtype", "tile"))
def batched_apsp(adjs: jnp.ndarray, *, use_kernel: bool = False,
                 method: str | None = None, symmetric: bool = True,
                 dtype: str = "float32",
                 tile: int | None = None) -> jnp.ndarray:
    """All-pairs shortest paths for a (B, N, N) stack of adjacencies.

    Three interchangeable algorithms (cross-validated in tests):

    * ``"fw"`` — batched vectorized Floyd-Warshall, O(N^3) with only a
      (B, N, N) temporary per step (unrolled x8 to amortize loop dispatch);
      the CPU default — its rank-1 broadcast-min step is memory-light,
      which on CPU beats squaring's (B, N, N, N) broadcast temporaries by
      an order of magnitude.
    * ``"squaring"`` — batched min-plus matrix squaring, O(N^3 log N) built
      from large tiled products; the TPU default at moderate N (the batched
      Pallas kernel runs one (N, N) min-plus tile per grid step) and forced
      whenever ``use_kernel`` is set.
    * ``"tiled"`` — blocked Floyd-Warshall over a (N/T, N/T) block grid
      (``kernels.minplus.apsp_tiled``), one matrix at a time via
      ``lax.map``: O(N^3) like fw but with panel-sized working sets, the
      TPU default past ``DEFAULT_TILED_N`` nodes (VMEM-resident tiles).

    ``symmetric`` (default) lets FW read only the contiguous row slice
    ``d[:, k, :]`` — valid for the undirected overlays every builder in
    this module produces (both edge directions are scattered).  Pass
    ``symmetric=False`` for directed inputs.  ``dtype`` selects the
    compute precision (``"float32"``/``"bfloat16"``); the result keeps it
    (``largest_cc_diameter`` re-widens downstream).
    """
    method = _resolve_method(use_kernel, method, adjs.shape[-1])
    assert dtype in ("float32", "bfloat16"), dtype
    adjs = adjs.astype(dtype)
    n = adjs.shape[-1]
    if method == "fw":
        def fw_body(k, d):
            if symmetric:
                col = row = d[:, k, :]
            else:
                col, row = d[:, :, k], d[:, k, :]
            return jnp.minimum(d, col[:, :, None] + row[:, None, :])

        return jax.lax.fori_loop(0, n, fw_body, adjs, unroll=8)

    if method == "tiled":
        from repro.kernels.minplus import ops as minplus_ops

        return jax.lax.map(
            lambda d: minplus_ops.apsp_tiled(
                d, tile=tile, force_kernel=use_kernel, symmetric=symmetric),
            adjs)

    assert method == "squaring", method
    n_iters = max(1, int(np.ceil(np.log2(max(n - 1, 2)))))

    def body(_, d):
        return _batched_minplus(d, d, use_kernel)

    return jax.lax.fori_loop(0, n_iters, body, adjs)


@functools.partial(jax.jit, static_argnames=("use_kernel", "method",
                                             "symmetric", "dtype", "tile"))
def batched_diameter(adjs: jnp.ndarray, *, use_kernel: bool = False,
                     method: str | None = None, symmetric: bool = True,
                     dtype: str = "float32",
                     tile: int | None = None) -> jnp.ndarray:
    """(B, N, N) adjacencies -> (B,) float32 largest-CC diameters."""
    d = batched_apsp(adjs, use_kernel=use_kernel, method=method,
                     symmetric=symmetric, dtype=dtype, tile=tile)
    return jax.vmap(largest_cc_diameter)(d)


# ---------------------------------------------------------------------------
# memory model
# ---------------------------------------------------------------------------

def workingset_bytes(chunk: int, n: int, method: str = "fw", *,
                     dtype: str = "float32", tile: int | None = None,
                     use_kernel: bool = False) -> int:
    """Modeled peak device working set of one engine call, per method.

    * ``fw`` (and kernel/TPU squaring): the (chunk, N, N) carry plus the
      rank-1 broadcast temporary and XLA's copy slack — 8 N^2 slabs per
      batch item (empirically calibrated against the previous engine).
    * CPU-oracle ``squaring``: the dense (chunk, N, N, N) broadcast-min
      temporary dominates everything else.
    * ``tiled``: the (chunk, N, N) input stack (``lax.map`` holds it
      whole) plus ONE matrix in flight — two padded copies and three
      (tile, N) panels — the whole point of the blocked engine.
    """
    item = 2 if dtype == "bfloat16" else 4
    if method == "squaring" and not (use_kernel
                                     or jax.default_backend() == "tpu"):
        return item * chunk * n ** 3
    if method == "tiled":
        from repro.kernels.minplus.ops import default_tile

        t = tile or default_tile(n)
        npad = -(-n // t) * t
        return item * (chunk * n * n + 2 * npad * npad + 3 * t * npad)
    return item * chunk * n * n * 8


def default_chunk(n: int, method: str = "fw",
                  budget_bytes: int | None = None, *,
                  dtype: str = "float32", tile: int | None = None,
                  use_kernel: bool = False) -> int:
    """Largest batch chunk whose modeled working set (``workingset_bytes``,
    which knows the per-method temporaries) stays under the budget.

    The budget defaults to ``REPRO_APSP_MEM_BYTES`` when set, else 256 MiB.
    Always >= 1: a single matrix must fit regardless (at N=4096 fp32 one
    fw item models at ~512 MiB — the engine then simply runs chunk=1).
    """
    if budget_bytes is None:
        budget_bytes = _opt("budget_bytes") or DEFAULT_BUDGET_BYTES
    one = workingset_bytes(1, n, method, dtype=dtype, tile=tile,
                           use_kernel=use_kernel)
    fixed = 0
    if method == "tiled":
        # panels + padded copies are shared across the chunk, not per-item
        item = 2 if dtype == "bfloat16" else 4
        fixed = one - item * n * n
        one = item * n * n
    return max(1, (budget_bytes - fixed) // max(1, one))


def quantize_latency(adjs: np.ndarray, bits: int = 16):
    """Quantize finite latencies to a uniform ``2**bits - 1``-level grid.

    Only ``is_edge`` entries move: the 0 diagonal and the 1e9 INF sentinel
    pass through BIT-EXACT, so ``largest_cc_diameter``'s ``INF / 2``
    connectivity test stays provable on quantized inputs.  Returns
    ``(quantized, scale)``; per-edge error is at most ``scale / 2``, so a
    shortest path of H hops is off by at most ``H * scale / 2``.
    """
    a = np.asarray(adjs, np.float32)
    mask = np.asarray(is_edge(a))
    if not mask.any():
        return a.copy(), 0.0
    levels = (1 << bits) - 1
    scale = float(a[mask].max()) / levels
    q = np.where(mask, np.rint(a / max(scale, 1e-30)) * scale, a)
    return q.astype(np.float32), scale


# ---------------------------------------------------------------------------
# host facade (streaming)
# ---------------------------------------------------------------------------

def _observe_call(method: str, key, seconds: float, ws_bytes: int) -> None:
    if not REGISTRY.enabled:
        return
    _APSP_SECONDS.labels(method=method,
                         phase=jit_phase("batcheval.apsp", key)).observe(
        seconds)
    _APSP_WORKINGSET.set(ws_bytes)


def _stream(src, b: int, n: int, fn, *, chunk: int, method: str,
            compute_dtype: str, quantize: bool, symmetric: bool,
            use_kernel: bool, tile: Optional[int], ws_bytes: int):
    """Drive ``fn`` over fixed-size chunks of ``src``, never holding more
    than one (chunk, N, N) block on host or device.  The trailing partial
    chunk is padded by repeating its first matrix so every device call has
    the SAME compiled shape (one trace, not one per remainder)."""
    outs = []
    calls = 0
    max_scale = 0.0
    single = b <= chunk
    for lo in range(0, b, chunk):
        hi = min(b, lo + chunk)
        blk = np.asarray(src.block(lo, hi), np.float32)
        if quantize:
            blk, scale = quantize_latency(blk)
            max_scale = max(max_scale, scale)
        if not single and hi - lo < chunk:
            blk = np.concatenate(
                [blk, np.repeat(blk[:1], chunk - (hi - lo), axis=0)], axis=0)
        t0 = time.perf_counter()
        res = np.asarray(fn(jnp.asarray(blk)))
        _observe_call(method,
                      (blk.shape[0], n, use_kernel, method, symmetric,
                       compute_dtype, tile),
                      time.perf_counter() - t0, ws_bytes)
        outs.append(res[:hi - lo])
        calls += 1
    return outs, calls, max_scale


def diameters(adjs, *, use_kernel: bool = False, method: str | None = None,
              symmetric: bool = True, chunk: int | None = None,
              dtype: str | None = None, tile: int | None = None,
              exact_rtol: float | None = None) -> np.ndarray:
    """Diameters for a batch of adjacencies, as a host (B,) float32 array.

    ``adjs`` is a (B, N, N) array or any lazy block source (``__len__``,
    ``.n``, ``.block(lo, hi)`` — e.g. :class:`RingBlockSource`).  The batch
    is STREAMED through fixed-size device chunks: peak memory is one
    (chunk, N, N) block plus the method's temporaries, never the whole
    batch — B=64 at N=4096 runs on a single host in a few hundred MB.

    ``dtype`` picks the evaluation precision: ``"float32"`` (exact),
    ``"bfloat16"`` (half-traffic compute), or ``"int16"`` (latencies
    quantized to a 16-bit grid, evaluated in f32).  Reduced-precision runs
    re-score a probe subset in float32 and, if the measured relative error
    exceeds ``exact_rtol`` (default 0.05), fall back to a full float32
    rerun — callers always get a result within the bound or exact.
    All knobs resolve through ``eval_options`` / ``REPRO_APSP_*`` env vars.
    """
    src = _as_source(adjs)
    b, n = len(src), src.n
    if b == 0:
        return np.zeros((0,), np.float32)
    use_kernel = bool(use_kernel or _opt("use_kernel"))
    method = _opt("method", method)
    if method is None:
        method = _auto_method(use_kernel, n,
                              int(os.environ.get("REPRO_APSP_TILED_N",
                                                 DEFAULT_TILED_N)))
    assert method in METHODS, method
    dtype = _opt("dtype", dtype) or "float32"
    assert dtype in DTYPES, dtype
    tile = _opt("tile", tile)
    chunk = _opt("chunk", chunk) or default_chunk(
        n, method, dtype=dtype, tile=tile, use_kernel=use_kernel)
    rtol = _opt("exact_rtol", exact_rtol)
    if rtol is None and dtype != "float32":
        rtol = DEFAULT_EXACT_RTOL
    compute_dtype = "bfloat16" if dtype == "bfloat16" else "float32"
    quantize = dtype == "int16"
    ws = workingset_bytes(min(b, chunk), n, method, dtype=compute_dtype,
                          tile=tile, use_kernel=use_kernel)

    def run(cdt: str, quant: bool):
        fn = lambda blk: batched_diameter(  # noqa: E731
            blk, use_kernel=use_kernel, method=method, symmetric=symmetric,
            dtype=cdt, tile=tile)
        return _stream(src, b, n, fn, chunk=chunk, method=method,
                       compute_dtype=cdt, quantize=quant,
                       symmetric=symmetric, use_kernel=use_kernel,
                       tile=tile, ws_bytes=ws)

    if b <= chunk:
        # small batches keep the legacy one-shot span (and its exact
        # unpadded shape, preserving bit-parity with the pre-streaming path)
        with jit_span("batcheval.diameters",
                      key=(b, n, use_kernel, method, symmetric, dtype)):
            outs, calls, max_scale = run(compute_dtype, quantize)
    else:
        outs, calls, max_scale = run(compute_dtype, quantize)
    out = np.concatenate(outs) if len(outs) > 1 else outs[0]

    rep = {"b": b, "n": n, "method": method, "dtype": dtype, "chunk": chunk,
           "tile": tile, "workingset_bytes": ws, "device_calls": calls,
           "quant_scale": max_scale, "quant_rel_err": 0.0, "fallback": False}
    if dtype != "float32":
        rel, out, fellback = _verify_quantized(src, b, out, rtol, run)
        rep["quant_rel_err"], rep["fallback"] = rel, fellback
        if fellback:
            rep["dtype"] = "float32"
    _report.data = rep
    return out


def _verify_quantized(src, b: int, out: np.ndarray, rtol: Optional[float],
                      run) -> tuple:
    """Measure reduced-precision error on float32 probes; past ``rtol``,
    re-run the whole batch exactly (the exactness-fallback contract)."""
    probes = np.arange(0, b, max(1, b // 8))[:8]
    ref = np.concatenate([
        np.asarray(batched_diameter(
            jnp.asarray(np.asarray(src.block(int(i), int(i) + 1),
                                   np.float32))))
        for i in probes])
    denom = np.maximum(np.abs(ref), 1e-6)
    rel = float(np.max(np.abs(out[probes] - ref) / denom)) if len(ref) else 0.0
    if REGISTRY.enabled:
        _APSP_QUANT_ERR.set(rel)
    if rtol is not None and rel > rtol:
        _APSP_FALLBACKS.inc()
        outs, _, _ = run("float32", False)
        out = np.concatenate(outs) if len(outs) > 1 else outs[0]
    return rel, out, bool(rtol is not None and rel > rtol)


def apsp_matrices(adjs, *, use_kernel: bool = False,
                  method: str | None = None, symmetric: bool = True,
                  chunk: int | None = None, dtype: str | None = None,
                  tile: int | None = None) -> np.ndarray:
    """Full (B, N, N) float32 APSP distance matrices, streamed per chunk.

    The matrix-returning sibling of ``diameters`` for consumers that need
    distances (the churn engine's rebuild, routing stretch): same method /
    chunk / dtype resolution and the same ``repro_apsp_seconds``
    instrumentation, with the result re-widened to float32 on host.  The
    HOST output is dense (the caller asked for it); only device memory is
    bounded.  No probe-verification here — reduced precision is the
    caller's explicit contract for distances.
    """
    src = _as_source(adjs)
    b, n = len(src), src.n
    if b == 0:
        return np.zeros((0, n, n), np.float32)
    use_kernel = bool(use_kernel or _opt("use_kernel"))
    method = _opt("method", method)
    if method is None:
        method = _auto_method(use_kernel, n,
                              int(os.environ.get("REPRO_APSP_TILED_N",
                                                 DEFAULT_TILED_N)))
    dtype = _opt("dtype", dtype) or "float32"
    tile = _opt("tile", tile)
    chunk = _opt("chunk", chunk) or default_chunk(
        n, method, dtype=dtype, tile=tile, use_kernel=use_kernel)
    compute_dtype = "bfloat16" if dtype == "bfloat16" else "float32"
    ws = workingset_bytes(min(b, chunk), n, method, dtype=compute_dtype,
                          tile=tile, use_kernel=use_kernel)

    def fn(blk):
        d = batched_apsp(blk, use_kernel=use_kernel, method=method,
                         symmetric=symmetric, dtype=compute_dtype, tile=tile)
        return d.astype(jnp.float32)

    outs, calls, max_scale = _stream(
        src, b, n, fn, chunk=chunk, method=method,
        compute_dtype=compute_dtype, quantize=dtype == "int16",
        symmetric=symmetric, use_kernel=use_kernel, tile=tile, ws_bytes=ws)
    _report.data = {"b": b, "n": n, "method": method, "dtype": dtype,
                    "chunk": chunk, "tile": tile, "workingset_bytes": ws,
                    "device_calls": calls, "quant_scale": max_scale,
                    "quant_rel_err": 0.0, "fallback": False}
    return np.concatenate(outs) if len(outs) > 1 else outs[0]


def diameters_of_rings(w: np.ndarray, genomes, *, use_kernel: bool = False,
                       method: str | None = None,
                       chunk: int | None = None,
                       dtype: str | None = None) -> np.ndarray:
    """Score B K-ring genomes by overlay diameter, streaming the adjacency
    assembly chunk-by-chunk (never a dense (B, N, N) host tensor)."""
    return diameters(RingBlockSource(w, genomes), use_kernel=use_kernel,
                     method=method, chunk=chunk, dtype=dtype)


# ---------------------------------------------------------------------------
# sharded compute (multi-device)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _sharded_diameter_fn(mesh, axis: str, use_kernel: bool, method: str,
                         symmetric: bool, dtype: str, tile: Optional[int]):
    from jax.sharding import PartitionSpec as P

    fn = jax.shard_map(
        lambda a: batched_diameter(a, use_kernel=use_kernel, method=method,
                                   symmetric=symmetric, dtype=dtype,
                                   tile=tile),
        mesh=mesh, in_specs=(P(axis),), out_specs=P(axis))
    return jax.jit(fn)


def diameters_sharded(adjs, *, mesh=None, axis: str = "batch",
                      use_kernel: bool = False, method: str | None = None,
                      symmetric: bool = True, dtype: str | None = None,
                      tile: int | None = None) -> np.ndarray:
    """``diameters`` with the batch axis sharded over a device mesh.

    Follows the ``parallel_ring_shmap`` pattern: pad B to a multiple of
    the mesh axis, place the stack with a ``NamedSharding``, and run
    ``batched_diameter`` per shard under ``jax.shard_map`` (no
    collectives — each device scores its own sub-batch).  With no mesh, a
    1D ``launch.mesh.make_eval_mesh`` over all local devices is built; on
    a single device this degrades to the streaming facade.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    adjs = np.asarray(adjs, np.float32)
    assert adjs.ndim == 3 and adjs.shape[1] == adjs.shape[2], adjs.shape
    b, n = adjs.shape[0], adjs.shape[-1]
    if b == 0:
        return np.zeros((0,), np.float32)
    if mesh is None:
        from repro.launch.mesh import make_eval_mesh

        mesh = make_eval_mesh(axis=axis)
    k = int(mesh.shape[axis])
    if k <= 1:
        return diameters(adjs, use_kernel=use_kernel, method=method,
                         symmetric=symmetric, dtype=dtype, tile=tile)
    use_kernel = bool(use_kernel or _opt("use_kernel"))
    method = _opt("method", method) or _auto_method(use_kernel, n)
    dtype = _opt("dtype", dtype) or "float32"
    tile = _opt("tile", tile)
    compute_dtype = "bfloat16" if dtype == "bfloat16" else "float32"
    if dtype == "int16":
        adjs, _ = quantize_latency(adjs)
    pad = (-b) % k
    if pad:
        adjs = np.concatenate([adjs, np.repeat(adjs[:1], pad, axis=0)],
                              axis=0)
    fn = _sharded_diameter_fn(mesh, axis, use_kernel, method, symmetric,
                              compute_dtype, tile)
    placed = jax.device_put(adjs, NamedSharding(mesh, P(axis)))
    t0 = time.perf_counter()
    out = np.asarray(fn(placed))
    per = adjs.shape[0] // k
    ws = workingset_bytes(per, n, method, dtype=compute_dtype, tile=tile,
                          use_kernel=use_kernel)
    _observe_call(method, ("sharded", k, per, n, use_kernel, method,
                           symmetric, compute_dtype, tile),
                  time.perf_counter() - t0, ws)
    _report.data = {"b": b, "n": n, "method": method, "dtype": dtype,
                    "chunk": per, "tile": tile, "workingset_bytes": ws,
                    "device_calls": 1, "devices": k, "quant_rel_err": 0.0,
                    "fallback": False}
    return out[:b]


@functools.lru_cache(maxsize=32)
def _rowshard_fn(mesh, axis: str, npad: int, n_iters: int):
    from jax.sharding import PartitionSpec as P

    def local(loc):
        def squaring(_, loc):
            full = jax.lax.all_gather(loc, axis, axis=0, tiled=True)

            def pivot(k, acc):
                col = jax.lax.dynamic_slice_in_dim(loc, k, 1, axis=1)
                row = jax.lax.dynamic_slice_in_dim(full, k, 1, axis=0)
                return jnp.minimum(acc, col + row)

            return jax.lax.fori_loop(0, npad, pivot, loc, unroll=8)

        return jax.lax.fori_loop(0, n_iters, squaring, loc)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(axis, None),),
                       out_specs=P(axis, None))
    return jax.jit(fn)


def apsp_rowshard(adj: np.ndarray, *, mesh=None,
                  axis: str = "rows") -> np.ndarray:
    """APSP of ONE (N, N) matrix with the ROW-BLOCK axis sharded.

    Min-plus squaring where each device owns an (N/k, N) row block and
    re-gathers the full matrix once per squaring (``all_gather`` over the
    mesh axis, log2(N) rounds) — the row-parallel complement of
    ``diameters_sharded`` for matrices too large to score one-per-device.
    Pads N to a mesh multiple with isolated singleton nodes.
    """
    adj = np.asarray(adj, np.float32)
    assert adj.ndim == 2 and adj.shape[0] == adj.shape[1], adj.shape
    n = adj.shape[0]
    if mesh is None:
        from repro.launch.mesh import make_eval_mesh

        mesh = make_eval_mesh(axis=axis)
    k = int(mesh.shape[axis])
    npad = -(-n // k) * k
    if npad != n:
        padded = np.full((npad, npad), float(INF), np.float32)
        padded[np.arange(npad), np.arange(npad)] = 0.0
        padded[:n, :n] = adj
        adj = padded
    n_iters = max(1, int(np.ceil(np.log2(max(npad - 1, 2)))))
    from jax.sharding import NamedSharding, PartitionSpec as P

    fn = _rowshard_fn(mesh, axis, npad, n_iters)
    placed = jax.device_put(adj, NamedSharding(mesh, P(axis)))
    t0 = time.perf_counter()
    out = np.asarray(fn(placed))
    item = 4
    ws = item * (npad * npad + 2 * (npad // k) * npad)
    _observe_call("squaring", ("rowshard", k, npad),
                  time.perf_counter() - t0, ws)
    return out[:n, :n]
