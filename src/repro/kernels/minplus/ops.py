"""jit'd public wrappers for the min-plus kernels (padding + dispatch).

On TPU the Pallas kernels run compiled; on CPU (this container) they run in
interpret mode for correctness validation, and callers that need speed use
the jnp oracles (``repro.core.batcheval`` picks per backend).

Blocks are chosen ADAPTIVELY from the operand shape: a 20-node product pads
to 24 (the next 8-multiple), not to 128 — padding with +INF is semantically
neutral (padded k entries contribute INF + x and never win the min; padded
rows/cols are sliced off), but an 128-block pad at N=20 was 40x wasted
work.  On TPU, shapes >= 128 keep the 128 lane-aligned block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import (INF, apsp_tiled_pallas, minplus_pallas,
                     minplus_pallas_batched)
from .ref import apsp_tiled_ref, minplus_batched_ref, minplus_ref


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _auto_block(*dims: int) -> int:
    """Smallest 8-multiple covering the largest dim, capped at 128.  Below
    the cap the block spans the whole padded operand, which Mosaic accepts
    at any 8-multiple; larger shapes are gridded over lane-aligned
    128-blocks."""
    return min(128, _ceil_to(max(max(dims), 8), 8))


def default_tile(n: int, cap: int = 256) -> int:
    """Tile for the blocked-FW APSP, one the chip accepts in f32 and bf16.

    Up to ``cap`` nodes the whole padded matrix is one block, which Mosaic
    takes at any multiple of 16 (bf16's sublane tile).  Past it a (T, T)
    block of a larger array must be lane-aligned, so T is the multiple of
    128 up to ``cap`` that pads N least, the larger on a tie (N=300 tiles
    as 3 x 128, not 2 x 256; N=986 as 4 x 256).
    """
    if n <= cap:
        return _ceil_to(max(n, 16), 16)
    return min(range(128, cap + 1, 128), key=lambda t: (_ceil_to(n, t), -t))


def _pad_to(x: jnp.ndarray, mult: int, fill: float) -> jnp.ndarray:
    *lead, m, n = x.shape
    pm = (-m) % mult
    pn = (-n) % mult
    if pm == 0 and pn == 0:
        return x
    pad = [(0, 0)] * len(lead) + [(0, pm), (0, pn)]
    return jnp.pad(x, pad, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def minplus(a: jnp.ndarray, b: jnp.ndarray, block: int | None = None,
            interpret: bool | None = None) -> jnp.ndarray:
    """Min-plus product with INF padding to (adaptive) block multiples."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, n = a.shape[0], b.shape[1]
    if block is None:
        block = _auto_block(m, a.shape[1], n)
    a32 = _pad_to(a.astype(jnp.float32), block, INF)
    b32 = _pad_to(b.astype(jnp.float32), block, INF)
    out = minplus_pallas(a32, b32, bm=block, bn=block, bk=block,
                         interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("block", "force_kernel"))
def minplus_batched(a: jnp.ndarray, b: jnp.ndarray, block: int | None = None,
                    force_kernel: bool = False) -> jnp.ndarray:
    """Batched min-plus product ``(B, M, K) x (B, K, N) -> (B, M, N)``.

    Backend dispatch: on TPU the Pallas kernel runs compiled with the batch
    as the outermost grid axis; everywhere else the vmapped jnp oracle is
    used (the interpret-mode kernel is far too slow for bulk evaluation —
    ``force_kernel`` exists so tests can still exercise the kernel path).
    """
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or force_kernel):
        return minplus_batched_ref(a, b)
    m, n = a.shape[1], b.shape[2]
    if block is None:
        block = _auto_block(m, a.shape[2], n)
    a32 = _pad_to(a.astype(jnp.float32), block, INF)
    b32 = _pad_to(b.astype(jnp.float32), block, INF)
    out = minplus_pallas_batched(a32, b32, bm=block, bn=block, bk=block,
                                 interpret=not on_tpu)
    return out[:, :m, :n]


@functools.partial(jax.jit, static_argnames=("tile", "force_kernel",
                                             "interpret", "symmetric"))
def apsp_tiled(d: jnp.ndarray, tile: int | None = None, *,
               force_kernel: bool = False, interpret: bool | None = None,
               symmetric: bool = False) -> jnp.ndarray:
    """Blocked Floyd-Warshall APSP of one (N, N) adjacency, memory-bounded.

    Pads N to a ``tile`` multiple with INF (padded nodes are unreachable
    and sliced off), then runs the (N/T, N/T) block-grid engine: the Pallas
    kernel on TPU (or under ``force_kernel``, interpret mode off-TPU), the
    bit-identical jnp twin ``ref.apsp_tiled_ref`` otherwise.  Keeps the
    input dtype (fp32 or bf16).  ``symmetric`` enables the ref's
    column-panel-as-transpose shortcut — bitwise-safe for the undirected
    overlays this repo builds; pass ``False`` for directed inputs.
    """
    n = d.shape[-1]
    assert d.ndim == 2 and d.shape[0] == n, d.shape
    if tile is None:
        tile = default_tile(n)
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    dp = _pad_to(d, tile, INF)
    if on_tpu or force_kernel:
        out = apsp_tiled_pallas(dp, tile=tile, interpret=interpret)
    else:
        out = apsp_tiled_ref(dp, tile, symmetric=symmetric)
    return out[:n, :n]


__all__ = ["minplus", "minplus_batched", "minplus_ref", "minplus_batched_ref",
           "apsp_tiled", "default_tile"]
