"""Cross-pod gradient compression: manual DP over the ``pod`` axis with the
int8 ring all-reduce, auto-SPMD within each pod.

The multi-pod baseline lets the partitioner all-reduce gradients over
("pod", "data") in one fused collective — the pod hop crosses DCN at full
width.  This variant makes the pod axis MANUAL (``shard_map`` with
``axis_names={"pod"}``): each pod runs the standard train step body
(microbatching, remat, ZeRO grad shardings — all inherited from
``train_step``) over its half of the batch, and the pod-level reduction is
the paper-adjacent piece: an int8-quantized RING reduce over ``ppermute``
along the DGRO-ordered pod ring (repro.train.collectives), 4x less DCN
traffic than fp32.

Trades: quantization noise (bounded by max|g|/254, optionally
error-fed-back) for a 4x cut of the slowest link's traffic.  §Perf
hillclimb C measures the collective-term delta from the compiled HLO.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from .collectives import compressed_grad_allreduce
from .train_step import TrainConfig, TrainState, train_step

PyTree = Any


def pod_compressed_train_step(
    cfg: ArchConfig,
    tc: TrainConfig,
    mesh: Mesh,
    state_shapes: TrainState,
    batch_shapes: Dict[str, Any],
    pod_axis: str = "pod",
    inner_data_axes: Tuple[str, ...] = ("data",),
    grad_shardings=None,
):
    """Builds the hybrid step fn.  In partial-manual shard_map the specs
    mention ONLY the manual axis: params/opt replicate across pods (P()),
    the batch splits its leading dim over pods, and the within-pod
    data/model sharding flows through the auto axes."""

    def transform(grads):
        mean, _err = compressed_grad_allreduce(grads, pod_axis)
        return mean

    def body(state: TrainState, batch: Dict[str, jnp.ndarray]):
        new_state, metrics = train_step(
            cfg, tc, state, batch, mesh=mesh, data_axes=inner_data_axes,
            grad_shardings=grad_shardings, grad_transform=transform)
        metrics["loss"] = jax.lax.pmean(metrics["loss"], pod_axis)
        return new_state, metrics

    pods = mesh.shape[pod_axis]
    state_specs = jax.tree.map(lambda _: P(), state_shapes)

    def batch_spec(leaf):
        if leaf.shape and leaf.shape[0] % pods == 0 and leaf.shape[0] >= pods:
            return P(pod_axis, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))

    batch_specs_tree = jax.tree.map(batch_spec, batch_shapes)
    metric_specs = {"loss": P(), "ce": P(), "aux": P(), "n_tok": P(),
                    "grad_norm": P()}

    return shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, batch_specs_tree),
        out_specs=(state_specs, metric_specs),
        axis_names={pod_axis},          # pod manual; data/model stay auto
        check_vma=False,
    )
