"""Roofline-term derivation from compiled dry-run artifacts (TPU v5e model).

    compute_s    = HLO_FLOPs_per_device / PEAK_FLOPS
    memory_s     = HLO_bytes_per_device / HBM_BW
    collective_s = collective_bytes_per_device / ICI_BW

``cost_analysis()`` on an SPMD-partitioned executable reports the PER-DEVICE
program, so the terms need no further division by chip count.  Collective
bytes are parsed from the compiled HLO text: every (possibly async-start)
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute,
with standard ring-transfer factors applied per op kind and group size.

MODEL_FLOPS = 6*N*D (N = active params, D = tokens per step) is the "useful
work" cross-check: MODEL_FLOPS / (HLO_FLOPs * chips) exposes remat recompute,
masked-attention waste and dispatch overhead.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

# --- TPU v5e hardware model (per chip) -------------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (conservative single-link)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<res>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


@dataclasses.dataclass
class Collective:
    op: str
    result_bytes: int
    group_size: int
    transfer_bytes: float    # ring-model bytes sent per device


def _shape_bytes(tok: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(tok):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total = max(total, n * _DTYPE_BYTES[dtype])
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def parse_collectives(hlo_text: str, default_group: int = 1) -> List[Collective]:
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        res_bytes = _shape_bytes(m.group("res"))
        g = _group_size(line, default_group)
        if g <= 1 and op != "collective-permute":
            continue
        if op == "all-reduce":
            xfer = 2.0 * res_bytes * (g - 1) / max(g, 1)
        elif op == "all-gather":
            # result holds the gathered value; each device sends its shard
            xfer = res_bytes * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            # result is the scattered shard; input = result * g
            xfer = res_bytes * (g - 1)
        elif op == "all-to-all":
            xfer = res_bytes * (g - 1) / max(g, 1)
        else:  # collective-permute
            xfer = float(res_bytes)
        out.append(Collective(op, res_bytes, g, xfer))
    return out


@dataclasses.dataclass
class Roofline:
    flops: float               # per device
    hbm_bytes: float           # per device
    collective_bytes: float    # per device (ring-model transferred)
    collective_raw_bytes: float  # naive sum of collective operand sizes
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    n_collectives: int
    by_op: Dict[str, float]

    def to_dict(self):
        return dataclasses.asdict(self)


def roofline_from(cost: Dict, hlo_text: str) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    colls = parse_collectives(hlo_text)
    cbytes = sum(c.transfer_bytes for c in colls)
    craw = sum(c.result_bytes for c in colls)
    by_op: Dict[str, float] = {}
    for c in colls:
        by_op[c.op] = by_op.get(c.op, 0.0) + c.transfer_bytes
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm / HBM_BW
    collective_s = cbytes / ICI_BW
    dom = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]
    return Roofline(flops=flops, hbm_bytes=hbm, collective_bytes=cbytes,
                    collective_raw_bytes=craw, compute_s=compute_s,
                    memory_s=memory_s, collective_s=collective_s,
                    dominant=dom, n_collectives=len(colls), by_op=by_op)


def model_flops(cfg, n_tokens: int, n_active_params: int) -> float:
    """6 * N_active * D (the standard training-FLOPs estimate; for inference
    steps callers pass the per-step token count)."""
    return 6.0 * n_active_params * n_tokens


def active_param_count(cfg, params_shapes) -> int:
    """Active params per token: total minus the non-routed share of experts."""
    import jax

    total = sum(int(l.size) for l in jax.tree.leaves(params_shapes))
    if cfg.n_experts == 0:
        return total
    leaves, _ = (jax.tree_util.tree_flatten_with_path(params_shapes))
    moe_params = sum(
        int(l.size) for p, l in leaves
        if "moe" in jax.tree_util.keystr(p)
        and re.search(r"w_(gate|up|down)", jax.tree_util.keystr(p)))
    active = total - moe_params + int(moe_params * cfg.top_k / cfg.n_experts)
    return active
