"""Distributed-optimization collectives: int8-compressed ring all-reduce
with error feedback, over the DGRO-ordered ring.

The DCN-level gradient all-reduce is a RING reduce-scatter + all-gather over
``ppermute``; the ring ORDER is the mesh's device order along the data axis
— which ``repro.launch.mesh`` builds from the DGRO ring optimization (the
paper's technique applied to the collective plane, DESIGN.md §2/§5).

Compression: per-chunk symmetric int8 quantization (scale = max|x|/127),
4x less DCN traffic than fp32 (2x vs bf16).  Quantization error is returned
so the caller can apply error feedback (add the residual into the next
step's gradient) — keeping convergence unbiased in expectation.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

PyTree = Any


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _ring_allreduce_1d(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Ring all-reduce (reduce-scatter + all-gather) of a flat fp32 vector
    with int8-compressed hops.  x must divide by the axis size."""
    n = jax.lax.axis_size(axis)
    i = jax.lax.axis_index(axis)
    chunks = x.reshape(n, -1)
    fwd = [(j, (j + 1) % n) for j in range(n)]

    # --- reduce-scatter: after n-1 hops, rank i holds the full sum of
    # chunk (i+1) mod n ---
    def rs_body(step, acc):
        # each rank sends the chunk it currently accumulates for (i - step)
        send_idx = (i - step) % n
        q, s = _quantize(acc[send_idx])
        q_r = jax.lax.ppermute(q, axis, fwd)
        s_r = jax.lax.ppermute(s, axis, fwd)
        recv_idx = (i - step - 1) % n
        return acc.at[recv_idx].add(q_r.astype(jnp.float32) * s_r)

    acc = jax.lax.fori_loop(0, n - 1, rs_body, chunks)

    # --- all-gather: quantize each completed chunk ONCE and circulate the
    # quantized payload unchanged, so every rank dequantizes identical bits
    # (re-quantizing per hop would make DP ranks diverge) ---
    own_idx = (i + 1) % n
    q0, s0 = _quantize(acc[own_idx])
    out_q = jnp.zeros((n,) + q0.shape, jnp.int8).at[own_idx].set(q0)
    out_s = jnp.zeros((n,), jnp.float32).at[own_idx].set(s0)

    def ag_body(step, carry):
        out_q, out_s, q, s = carry
        q = jax.lax.ppermute(q, axis, fwd)
        s = jax.lax.ppermute(s, axis, fwd)
        idx = (i - step) % n          # chunk id that arrives at this step
        return (out_q.at[idx].set(q), out_s.at[idx].set(s), q, s)

    out_q, out_s, _, _ = jax.lax.fori_loop(0, n - 1, ag_body,
                                           (out_q, out_s, q0, s0))
    out = out_q.astype(jnp.float32) * out_s[:, None]
    return out.reshape(x.shape)


def ring_allreduce(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """int8-compressed ring all-reduce — call INSIDE shard_map.  ``x`` is a
    per-shard fp32 array of identical shape on every shard; returns the sum.
    """
    n = jax.lax.axis_size(axis)
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = _ring_allreduce_1d(flat, axis)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape).astype(x.dtype)


def compressed_grad_allreduce(grads: PyTree, axis: str = "data",
                              error_fb: PyTree | None = None,
                              ) -> Tuple[PyTree, PyTree]:
    """Mean-all-reduce per-shard gradients with int8 compression + error
    feedback — call INSIDE shard_map (manual-DP step; see
    examples/compressed_dp.py and tests/test_collectives.py).

    Returns (reduced_grads, new_error_feedback): the residual the local
    quantization dropped this step, to be added to next step's grads.
    """
    n = jax.lax.axis_size(axis)
    if error_fb is not None:
        grads = jax.tree.map(lambda g, e: g + e.astype(g.dtype), grads, error_fb)

    def reduce_one(g):
        return ring_allreduce(g, axis) / n

    mean = jax.tree.map(reduce_one, grads)

    def residual(g):
        q, s = _quantize(g.astype(jnp.float32))
        return g.astype(jnp.float32) - q.astype(jnp.float32) * s

    new_err = jax.tree.map(residual, grads)
    return mean, new_err
