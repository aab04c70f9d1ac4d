"""Mixture-of-Experts layer: top-k routing, capacity-based dispatch,
expert parallelism over the ``model`` mesh axis.

TPU-native design (DESIGN.md §5): activations are replicated over the model
axis between layers (Megatron-style), so expert parallelism needs NO
all-to-all — each model shard gathers the tokens routed to ITS experts
(identical routing computed on every shard), runs the dense per-expert
GEMMs at static capacity C = ceil(T * top_k * cf / E), scatters weighted
outputs back, and one all-reduce (psum over "model") combines shards.  The
collective volume equals dense-TP's MLP all-reduce — measured in §Roofline.

Two entry points with identical math (tested against each other):
  * ``moe_apply(..., mesh=None)``  — single-device path (smoke tests).
  * ``moe_apply(..., mesh=mesh)``  — shard_map EP path (dry-run/training).

Tokens over capacity are dropped (standard Switch/GShard semantics; the
router's load-balancing auxiliary loss keeps drop rates low).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs.base import ArchConfig
from .layers import dense_init

__all__ = ["init_moe", "moe_apply", "router_aux_loss"]


def init_moe(key, cfg: ArchConfig, dtype) -> Dict[str, jnp.ndarray]:
    d, fe, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    ks = jax.random.split(key, 4)
    scale = (2.0 / (d + fe)) ** 0.5
    return {
        "router": dense_init(ks[0], d, e, jnp.float32),   # fp32 (routing-sensitive)
        "w_gate": (jax.random.normal(ks[1], (e, d, fe), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, fe), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, fe, d), jnp.float32) * scale).astype(dtype),
    }


def _capacity(t: int, cfg: ArchConfig) -> int:
    c = int(t * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def _moe_local(router, w_gate, w_up, w_down, xf, *, cfg: ArchConfig,
               e_local: int, e_offset, axis: Optional[str]):
    """Per-shard MoE body.  xf: (T, d) local tokens (replicated over model);
    w_*: (e_local, ...) this shard's experts; e_offset: first expert id."""
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    c = _capacity(t, cfg)

    logits = xf.astype(jnp.float32) @ router                    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = jax.lax.top_k(probs, k)                         # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    eid_f = eid.reshape(-1)                                     # (T*k,)
    gate_f = gate.reshape(-1)
    tok_f = jnp.repeat(jnp.arange(t), k)

    # position of each routed copy within its expert's capacity buffer
    onehot = jax.nn.one_hot(eid_f, e, dtype=jnp.int32)          # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0)[jnp.arange(t * k), eid_f] - 1
    keep = pos < c

    # local experts only: ids relative to this shard
    lid = eid_f - e_offset
    mine = (lid >= 0) & (lid < e_local) & keep
    didx = jnp.where(mine, lid * c + pos, e_local * c)          # OOB -> dropped
    buf = jnp.zeros((e_local * c, d), xf.dtype)
    buf = buf.at[didx].set(xf[tok_f], mode="drop")

    h = buf.reshape(e_local, c, d)
    act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", h, w_up)
    out = jnp.einsum("ecf,efd->ecd", act, w_down).reshape(e_local * c, d)

    # gather back, weight by gate, accumulate the k copies per token
    picked = jnp.where(mine[:, None],
                       jnp.take(out, jnp.clip(didx, 0, e_local * c - 1), axis=0),
                       0.0)
    contrib = picked * gate_f[:, None].astype(picked.dtype)
    y = jnp.zeros((t, d), xf.dtype).at[tok_f].add(contrib)
    if axis is not None:
        y = jax.lax.psum(y, axis)
    return y, probs


def moe_apply(p: Dict[str, jnp.ndarray], x: jnp.ndarray, cfg: ArchConfig,
              mesh: Optional[Mesh] = None, model_axis: str = "model",
              data_axes: Tuple[str, ...] = ("data",),
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,S,d), router_probs (T,E) for the aux loss)."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    e = cfg.n_experts

    if mesh is None or model_axis not in mesh.shape:
        y, probs = _moe_local(p["router"], p["w_gate"], p["w_up"], p["w_down"],
                              xf, cfg=cfg, e_local=e, e_offset=0, axis=None)
        return y.reshape(b, s, d), probs

    n_shards = mesh.shape[model_axis]
    assert e % n_shards == 0, (e, n_shards)
    e_local = e // n_shards

    def body(router, wg, wu, wd, xl):
        shard_id = jax.lax.axis_index(model_axis)
        y, probs = _moe_local(router, wg, wu, wd, xl, cfg=cfg,
                              e_local=e_local, e_offset=shard_id * e_local,
                              axis=model_axis)
        return y, probs

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(data_axes, None)),
        out_specs=(P(data_axes, None), P(data_axes, None)),
        check_vma=False,
    )
    y, probs = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], xf)
    return y.reshape(b, s, d), probs


def router_aux_loss(probs: jnp.ndarray, eid_top1: Optional[jnp.ndarray] = None,
                    ) -> jnp.ndarray:
    """Switch-style load-balancing loss: E * sum_e f_e * p_e, where f_e is
    the fraction of tokens whose top-1 choice is e and p_e the mean router
    probability of e."""
    e = probs.shape[-1]
    if eid_top1 is None:
        eid_top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(eid_top1, e, dtype=jnp.float32), axis=0)
    pmean = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * pmean)
