"""Pure-jnp oracles for the min-plus kernels (unbatched, batched, tiled).

``apsp_tiled_ref`` is the CPU twin of the Pallas blocked Floyd-Warshall in
``kernel.apsp_tiled_pallas``: it sequences the SAME three per-k-block
phases over the SAME (tile, tile) block grid, so CPU CI exercises the
kernel's block logic bit-for-bit (min over floats is exact, so any
regrouping of the same candidate set — the kernel's row-blocked rank-1
loops vs the whole-matrix ones here — produces identical bits).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def minplus_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C[i, j] = min_k A[i, k] + B[k, j] (dense broadcast)."""
    return jnp.min(a[:, :, None] + b[None, :, :], axis=1)


def minplus_batched_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C[b, i, j] = min_k A[b, i, k] + B[b, k, j] (vmapped dense broadcast)."""
    return jax.vmap(minplus_ref)(a, b)


# ---------------------------------------------------------------------------
# blocked Floyd-Warshall (the tiled APSP fallback)
# ---------------------------------------------------------------------------

def fw_tile_ref(d: jnp.ndarray, *, symmetric: bool = False) -> jnp.ndarray:
    """Transitive closure of one (T, T) tile by rank-1 Floyd-Warshall.

    ``symmetric`` reads only the contiguous pivot row — bitwise equal to
    the general form on symmetric tiles (FW preserves symmetry exactly:
    the two update terms commute under +).
    """
    def body(k, d):
        row = jax.lax.dynamic_slice_in_dim(d, k, 1, axis=0)     # (1, T)
        col = row.T if symmetric else \
            jax.lax.dynamic_slice_in_dim(d, k, 1, axis=1)       # (T, 1)
        return jnp.minimum(d, col + row)

    return jax.lax.fori_loop(0, d.shape[0], body, d, unroll=4)


def _panel_update(p: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                  *, unroll: int = 8) -> jnp.ndarray:
    """``min(p, a ⊗ b)`` with the product taken against FROZEN a, b.

    Freezing matters: updating the operand mid-loop would admit ulp-level
    double-relaxation candidates the Pallas kernel (which reduces against
    the unmodified block) never sees, breaking bit parity.
    """
    def body(k, acc):
        col = jax.lax.dynamic_slice_in_dim(a, k, 1, axis=1)     # (M, 1)
        row = jax.lax.dynamic_slice_in_dim(b, k, 1, axis=0)     # (1, N)
        return jnp.minimum(acc, col + row)

    return jax.lax.fori_loop(0, a.shape[1], body, p, unroll=unroll)


def apsp_tiled_ref(d: jnp.ndarray, tile: int, *,
                   symmetric: bool = False) -> jnp.ndarray:
    """Blocked Floyd-Warshall APSP over a (tile, tile) block grid.

    For each diagonal block k (three phases, the classic blocked FW):

    1. close the (T, T) diagonal tile (rank-1 FW);
    2. relax the k-th row panel against the closed diagonal
       (``min(rowp, diag ⊗ rowp)``) and the column panel symmetrically;
    3. rank-1 outer update of the WHOLE matrix against the fresh panels
       (``min(d, colp ⊗ rowp)``) — the panels themselves are included
       (their extra candidates are valid path lengths, so the update is a
       no-op there up to fp rounding), which keeps the update a uniform
       2D block grid exactly like the Pallas kernel's.

    ``symmetric`` derives the column panel as ``rowp.T`` — bitwise equal
    to the general form on symmetric inputs, at 2/3 of the panel work.
    Requires ``d.shape[0] % tile == 0`` (callers pad with INF).
    """
    n = d.shape[0]
    assert n % tile == 0, (n, tile)
    nb = n // tile

    def kblock(kb, d):
        o = kb * tile
        diag = fw_tile_ref(jax.lax.dynamic_slice(d, (o, o), (tile, tile)),
                           symmetric=symmetric)
        rowp = jax.lax.dynamic_update_slice(
            jax.lax.dynamic_slice(d, (o, 0), (tile, n)), diag, (0, o))
        rowp = _panel_update(rowp, diag, rowp)
        if symmetric:
            colp = rowp.T
        else:
            colp = jax.lax.dynamic_update_slice(
                jax.lax.dynamic_slice(d, (0, o), (n, tile)), diag, (o, 0))
            colp = _panel_update(colp, colp, diag)
        d = jax.lax.dynamic_update_slice(d, rowp, (o, 0))
        d = jax.lax.dynamic_update_slice(d, colp, (0, o))
        return _panel_update(d, colp, rowp)

    return jax.lax.fori_loop(0, nb, kblock, d)
