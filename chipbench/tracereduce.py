"""From a profiler trace to the benchmark's device numbers.

:func:`flatten` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``) into
plain rows ``[plane, line, name, start_ns, dur_ns]``; :func:`reduce` turns
rows into busy time, the heaviest device operations, the longest idle gaps
labelled by what the host was doing, and the min-plus kernel time spent in
the incremental rebuild.  Keeping the rows plain lets a test check the
reduction on a small recorded trace.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence, Tuple

# what the reduction looks for, by name as the TPU trace shows it
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# a Pallas kernel as the TPU trace names it; inside the rebuild's program
# the only ones are the blocked Floyd-Warshall min-plus kernels
MINPLUS_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
# "%name = <shape> opcode(...)": the name and the opcode, for the breakdown
_OP = re.compile(r"^(\S+) = .*? ([a-z][a-z0-9-]*)\(")
# the rebuild runs ``batched_apsp`` as a program of its own; candidate
# scoring runs ``batched_diameter``
REBUILD_MODULE = re.compile(r"^jit_batched_apsp\b")
# host events that mark thread-pool bookkeeping rather than work
_HOST_NOISE = re.compile(r"^(ThreadpoolListener::|end: )")

Row = Tuple[str, str, str, float, float]


def flatten(path: str) -> List[Row]:
    """Every event of the device and host planes of one trace file."""
    from jax.profiler import ProfileData

    rows: List[Row] = []
    for plane in ProfileData.from_file(path).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)))
    return rows


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _top(totals: Dict[str, float], k: int = 10) -> List[list]:
    return [[name, s] for name, s in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def reduce(rows: Sequence[Row], window_s: float) -> Dict:
    """Device numbers of one traced slice of ``window_s`` seconds.

    ``busy_s`` is the union of the device's operation intervals, averaged
    over the devices that ran any; ``idle_gaps`` labels each gap between
    them with the host event that overlaps most of it, a benchmark
    annotation (``chipbench.*``) first.
    """
    ops = [r for r in rows if DEVICE_PLANE.match(r[0]) and r[1] == OPS_LINE]
    devices = sorted({r[0] for r in ops})
    busy = {d: _union((r[3], r[3] + r[4]) for r in ops if r[0] == d)
            for d in devices}
    busy_s = (sum(b - a for d in devices for a, b in busy[d])
              / len(devices) / 1e9) if devices else 0.0

    op_totals: Dict[str, float] = {}
    for r in ops:
        name = short_name(r[2])
        op_totals[name] = op_totals.get(name, 0.0) + r[4] / 1e9

    host = [r for r in rows if r[0] == HOST_PLANE and r[4] > 0
            and not _HOST_NOISE.match(r[2])]
    spans_idle: List[Tuple[float, float]] = []
    for d in devices[:1]:
        merged = busy[d]
        spans_idle = [(a, b) for (_, a), (b, _) in zip(merged, merged[1:])]
    spans_idle.sort(key=lambda g: g[0] - g[1])
    gaps = [[_label(host, a, b), (b - a) / 1e9] for a, b in spans_idle[:10]]

    modules = [r for r in rows if DEVICE_PLANE.match(r[0])
               and r[1] == MODULES_LINE and REBUILD_MODULE.match(r[2])]
    spans = _union((r[3], r[3] + r[4]) for r in modules)
    kernel_s = sum(r[4] for r in ops if MINPLUS_KERNEL.search(r[2])
                   and _inside(spans, r[3])) / 1e9
    return {"busy_s": busy_s, "window_s": float(window_s),
            "devices": len(devices), "device_ops": _top(op_totals),
            "idle_gaps": gaps, "rebuild_modules": len(modules),
            "rebuild_kernel_s": kernel_s}


def short_name(op: str) -> str:
    """``%closed_call.35 custom-call:tpu_custom_call`` from an HLO op's
    full text; a name with no such text is kept as it is."""
    m = _OP.match(op)
    if not m:
        return op
    target = re.search(r'custom_call_target="([^"]+)"', op)
    return f"{m.group(1)} {m.group(2)}" + (f":{target.group(1)}"
                                           if target else "")


def _inside(spans: List[List[float]], t: float) -> bool:
    lo, hi = 0, len(spans)
    while lo < hi:
        mid = (lo + hi) // 2
        if spans[mid][1] < t:
            lo = mid + 1
        else:
            hi = mid
    return lo < len(spans) and spans[lo][0] <= t <= spans[lo][1]


def _label(host: Sequence[Row], a: float, b: float) -> str:
    """The host event that overlaps most of [a, b]; annotations first."""
    best = {True: ("", 0.0), False: ("", 0.0)}
    for r in host:
        cover = min(b, r[3] + r[4]) - max(a, r[3])
        mine = r[2].startswith("chipbench.")
        if cover > best[mine][1]:
            best[mine] = (r[2], cover)
    if best[True][1] >= 0.5 * (b - a):
        return best[True][0]
    return best[False][0] or best[True][0] or "no host event"
