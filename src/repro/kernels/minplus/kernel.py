"""Pallas TPU kernels: tiled min-plus (tropical) products and blocked FW.

C[i, j] = min_k A[i, k] + B[k, j]

This is the inner step of the min-plus APSP used by ``repro.core.diameter``
and ``repro.core.batcheval`` — the paper's diameter computation is the hot
spot of both the Q-learning reward loop and the GA baseline.  Min-plus has
no multiply-accumulate, so it maps to the VPU (not the MXU).

Every product body is ``_minplus_rows``: rank-1 updates
``acc = min(acc, A[:, k] + B[k, :])`` over a STATIC loop on k, with the
accumulator walked ``_row_block`` rows at a time so the rows being reduced
stay in vregs.  The column ``A[:, k]`` is a static lane slice broadcast
across lanes and the row ``B[k, :]`` a static sublane load broadcast across
sublanes.  Nothing is sliced dynamically except whole row blocks (aligned
sublane offsets): Mosaic lowers neither a ``dynamic_slice`` of a value nor
a dynamic slice of a ref along the lane dimension.

Min over floats is exact and each candidate ``A[i, k] + B[k, j]`` is one
rounded add, so any regrouping of the same candidate set — this kernel's
row blocks, the oracles' broadcasts — gives identical bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = 1e9


def _row_block(m: int) -> int:
    """Accumulator rows per pass: 32 where they divide M (whole vregs in
    f32 and bf16 alike), else the largest of 16 and 8 that does."""
    for rb in (32, 16, 8):
        if m % rb == 0:
            return rb
    return m


def _minplus_rows(c_ref, a_ref, b_ref, o_ref):
    """``o = min(c, a ⊗ b)`` for refs a (M, K), b (K, N) and c, o (M, N).

    ``c`` and ``o`` may be the same ref (accumulate in place); ``b`` is
    never written, so it is the frozen operand even when ``c`` aliases it
    by value (the panel updates pass one tile as both).
    """
    m, k = a_ref.shape
    rb = _row_block(m)

    def rows(r, carry):
        i = pl.multiple_of(r * rb, rb)
        acc = c_ref[pl.ds(i, rb), :]
        a = a_ref[pl.ds(i, rb), :]
        for kk in range(k):
            acc = jnp.minimum(acc, a[:, kk:kk + 1] + b_ref[kk:kk + 1, :])
        o_ref[pl.ds(i, rb), :] = acc
        return carry

    jax.lax.fori_loop(0, m // rb, rows, 0)


def _minplus_kernel(a_ref, b_ref, o_ref):
    """One (bm, bn) output block; the K panels are the last grid axis, so
    the block stays resident in VMEM while they accumulate into it."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, INF)

    _minplus_rows(o_ref, a_ref, b_ref, o_ref)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_pallas_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched tiled min-plus: ``(B, M, K) x (B, K, N) -> (B, M, N)``.

    Grid (B, M/bm, N/bn, K/bk): the batch axis is the OUTERMOST grid
    dimension, so each batch element's output tiles are finished before the
    next element starts and the per-step VMEM footprint is that of one
    (bm, bk) x (bk, bn) product (the batch never touches VMEM as a whole).
    K is innermost (the TPU revisiting rule: the last grid axis is the
    sequential minor-most one).  Inputs must be fp32 with dims divisible by
    the blocks (``ops`` pads); a block must be a multiple of 128 or span
    its whole dimension for Mosaic to accept it.
    """
    bsz, m, k = a.shape
    bsz2, k2, n = b.shape
    assert bsz == bsz2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, bm, bn, bk)

    return pl.pallas_call(
        _minplus_kernel,
        grid=(bsz, m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((None, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),
            pl.BlockSpec((None, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, m, n), jnp.float32),
        interpret=interpret,
    )(a, b)


def minplus_pallas(a: jnp.ndarray, b: jnp.ndarray, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """Tiled min-plus product ``(M, K) x (K, N)``: the batched kernel on a
    unit batch."""
    return minplus_pallas_batched(a[None], b[None], bm=bm, bn=bn, bk=bk,
                                  interpret=interpret)[0]


# ---------------------------------------------------------------------------
# blocked Floyd-Warshall APSP (the tiled engine behind batcheval "tiled")
# ---------------------------------------------------------------------------
#
# Per diagonal block k, three kernels over the same (T, T) block grid as
# ``ref.apsp_tiled_ref`` (which is the bit-exact jnp twin):
#
#   1. ``_fw_diag_kernel``    — close the diagonal tile in VMEM (rank-1 FW,
#      sequential over T pivots: each pivot depends on the previous).
#   2. ``_panel_*_kernel``    — min(p, diag ⊗ p) / min(p, p ⊗ diag) for the
#      row/column panels, 1D grid over the panel's (T, T) blocks.
#   3. ``_outer_kernel``      — min(d, colp ⊗ rowp) over the FULL 2D
#      (N/T, N/T) block grid; each grid step reads one stationary output
#      tile plus one panel tile from each operand (K = T, single panel).
#
# VMEM per step at T=256 fp32: four tiles of 256 KiB, double-buffered —
# ~2 MiB, far under the ~16 MiB/core budget.


def _fw_diag_kernel(d_ref, o_ref):
    """Rank-1 Floyd-Warshall closure of one (T, T) tile, in place in VMEM.

    The pivot's row and column are picked by masked min-reductions (a
    dynamic pivot index may not slice the lane dimension); min over the
    pivot entry and +inf is that entry, bit for bit.
    """
    t = d_ref.shape[0]
    o_ref[...] = d_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)

    def body(k, carry):
        d = o_ref[...]
        col = jnp.min(jnp.where(lane == k, d, jnp.inf), axis=1, keepdims=True)
        row = jnp.min(jnp.where(sub == k, d, jnp.inf), axis=0, keepdims=True)
        o_ref[...] = jnp.minimum(d, col + row)
        return carry

    jax.lax.fori_loop(0, t, body, 0)


def _panel_left_kernel(diag_ref, p_ref, o_ref):
    """One (T, T) block of the row panel: o = min(p, diag ⊗ p)."""
    _minplus_rows(p_ref, diag_ref, p_ref, o_ref)


def _panel_right_kernel(p_ref, diag_ref, o_ref):
    """One (T, T) block of the column panel: o = min(p, p ⊗ diag)."""
    _minplus_rows(p_ref, p_ref, diag_ref, o_ref)


def _outer_kernel(d_ref, colp_ref, rowp_ref, o_ref):
    """One (T, T) output tile: o = min(d, colp_tile ⊗ rowp_tile)."""
    _minplus_rows(d_ref, colp_ref, rowp_ref, o_ref)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def apsp_tiled_pallas(d: jnp.ndarray, tile: int = 256,
                      interpret: bool = False) -> jnp.ndarray:
    """Blocked Floyd-Warshall APSP over a (N/T, N/T) Pallas block grid.

    ``d`` is one (N, N) adjacency (0 diag, INF non-edges) with N divisible
    by ``tile`` (``ops.apsp_tiled`` pads; ``ops.default_tile`` picks tiles
    the chip accepts).  Keeps dtype (fp32 or bf16).  Bit-identical to
    ``ref.apsp_tiled_ref`` on the same padded input.
    """
    n = d.shape[0]
    assert d.ndim == 2 and d.shape[1] == n, d.shape
    assert n % tile == 0, (n, tile)
    nb = n // tile
    dt = d.dtype

    def _call(kernel, grid, in_specs, out_specs, out_shape):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=jax.ShapeDtypeStruct(out_shape, dt),
            interpret=interpret)

    t = tile
    fw_diag = _call(
        _fw_diag_kernel, (1,),
        [pl.BlockSpec((t, t), lambda i: (0, 0))],
        pl.BlockSpec((t, t), lambda i: (0, 0)), (t, t))
    panel_left = _call(
        _panel_left_kernel, (nb,),
        [pl.BlockSpec((t, t), lambda j: (0, 0)),
         pl.BlockSpec((t, t), lambda j: (0, j))],
        pl.BlockSpec((t, t), lambda j: (0, j)), (t, n))
    panel_right = _call(
        _panel_right_kernel, (nb,),
        [pl.BlockSpec((t, t), lambda i: (i, 0)),
         pl.BlockSpec((t, t), lambda i: (0, 0))],
        pl.BlockSpec((t, t), lambda i: (i, 0)), (n, t))
    outer = _call(
        _outer_kernel, (nb, nb),
        [pl.BlockSpec((t, t), lambda i, j: (i, j)),
         pl.BlockSpec((t, t), lambda i, j: (i, 0)),
         pl.BlockSpec((t, t), lambda i, j: (0, j))],
        pl.BlockSpec((t, t), lambda i, j: (i, j)), (n, n))

    def kblock(kb, d):
        o = kb * t
        diag = fw_diag(jax.lax.dynamic_slice(d, (o, o), (t, t)))
        rowp = jax.lax.dynamic_update_slice(
            jax.lax.dynamic_slice(d, (o, 0), (t, n)), diag, (0, o))
        rowp = panel_left(diag, rowp)
        colp = jax.lax.dynamic_update_slice(
            jax.lax.dynamic_slice(d, (0, o), (n, t)), diag, (o, 0))
        colp = panel_right(colp, diag)
        d = jax.lax.dynamic_update_slice(d, rowp, (o, 0))
        d = jax.lax.dynamic_update_slice(d, colp, (0, o))
        return outer(d, colp, rowp)

    return jax.lax.fori_loop(0, nb, kblock, d)
