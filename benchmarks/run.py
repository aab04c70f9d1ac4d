"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (reduced CPU-scale defaults;
each figure module has CLI flags for the full-scale sweeps).

Gates are FIRST-CLASS: every figure declares in ``GATES`` whether it is
informational or carries a hard pass/fail condition, which boolean key in
its result dict the harness enforces, and which ``BENCH_*.json`` metric
records the latest measured value.  ``--list`` prints the registry with the
latest values without running anything.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--list]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import sys
import traceback
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Gate:
    """A figure's declared pass/fail contract.

    ``key`` names the boolean in the figure's result dict the harness
    enforces; ``None`` marks a purely informational figure (its soft
    indicators print but never fail the run).  ``bench_file`` /
    ``bench_metric`` (a dotted path) locate the latest measured value in
    the figure's emitted ``BENCH_*.json`` for ``--list``.
    """

    description: str
    key: Optional[str] = None
    bench_file: Optional[str] = None
    bench_metric: Optional[str] = None

    @property
    def hard(self) -> bool:
        return self.key is not None

    def passes(self, res: dict) -> bool:
        if self.key is None:
            return True
        if self.key not in res:
            raise KeyError(
                f"gate declares key {self.key!r} but the figure result "
                f"only has {sorted(res)}")
        return bool(res[self.key])


GATES = {
    "fig09": Gate("device rollout >= 10x host steps/s at N=32, E=8",
                  key="passes_gate", bench_file="BENCH_fig09_dqn.json",
                  bench_metric="rollout_gate.speedup"),
    "fig10": Gate("informational: DGRO norm-diam within 1.15x of GA"),
    "fig11-uniform": Gate("informational: adapt reduces mean diameter"),
    "fig11-gaussian": Gate("informational: adapt reduces mean diameter"),
    "fig15-fabric": Gate("informational: adapt reduces mean diameter"),
    "fig15-bitnode": Gate("informational: adapt reduces mean diameter"),
    "fig12": Gate("informational: best ring count M varies by setting"),
    "fig13": Gate("informational: dgro <= min(random, nearest) per size"),
    "fig17-bitnode": Gate("informational: dgro <= min(random, nearest)"),
    "fig14": Gate("batched construction >= 5x host loop at N=256, M=8 "
                  "and diameter parity <= 1.05",
                  key="passes_gate", bench_file="BENCH_fig14_parallel.json",
                  bench_metric="gate_speedup.speedup"),
    "fig15-batcheval": Gate("batched eval >= 5x scipy at the largest batch",
                            key="passes_gate"),
    "fig16-churn": Gate("incremental maintenance >= 5x full recompute "
                        "at N=128",
                        key="passes_gate", bench_file="BENCH_fig16_churn.json",
                        bench_metric="gate.speedup"),
    "fig17-service": Gate("query p99 stays bounded during in-flight reopt "
                          "and restart diameter == pre-crash snapshot",
                          key="passes_gate",
                          bench_file="BENCH_fig17_service.json",
                          bench_metric="gate.query_p99_ms_during_reopt"),
    "fig18-obs": Gate("instrumented throughput within 5% of disabled path, "
                      "scraped counters exact, histogram p99 within bucket",
                      key="passes_gate", bench_file="BENCH_fig18_obs.json",
                      bench_metric="gate.overhead_pct"),
    "fig19-routing": Gate("vmapped router >= 5x host per-pair loop at "
                          "P=1024, host parity at fixed seed, greedy "
                          "success 1.0",
                          key="passes_gate",
                          bench_file="BENCH_fig19_routing.json",
                          bench_metric="gate.speedup"),
    "fig20-scale": Gate("streamed facade bit-identical to the pre-engine "
                        "direct path at N<=256, tiled FW parity, and peak "
                        "working set < dense (B,N,N)/2 at the largest N",
                        key="passes_gate",
                        bench_file="BENCH_fig20_scale.json",
                        bench_metric="gate.largest_n_diam_per_s"),
    "fig21-hier": Gate("N=1e5 hier construct+maintain (>=200 churn events) "
                       "within CPU budget, hier diameter <= 1.5x flat exact "
                       "at small N, served distances lower-bound exact APSP, "
                       "flat serde byte-identical",
                       key="passes_gate", bench_file="BENCH_fig21_hier.json",
                       bench_metric="scale.events_per_s"),
    "roofline": Gate("informational: kernel roofline table renders"),
}


def _bench_value(gate: Gate) -> str:
    """Latest measured value for --list, from the figure's BENCH json."""
    if gate.bench_file is None:
        return "-"
    if not os.path.exists(gate.bench_file):
        return "(no run yet)"
    try:
        with open(gate.bench_file) as f:
            node = json.load(f)
        for part in (gate.bench_metric or "").split("."):
            node = node[part]
        return f"{node:.2f}" if isinstance(node, float) else str(node)
    except (KeyError, TypeError, ValueError) as e:
        return f"(unreadable: {e!r})"


def list_gates() -> None:
    print(f"{'figure':<16} {'gate':<6} {'latest':<14} condition")
    for name, gate in GATES.items():
        kind = "HARD" if gate.hard else "info"
        print(f"{name:<16} {kind:<6} {_bench_value(gate):<14} "
              f"{gate.description}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="minimal sizes (CI smoke)")
    ap.add_argument("--verbose", action="store_true",
                    help="stream per-figure detail output")
    ap.add_argument("--list", action="store_true",
                    help="print figure -> gate -> latest BENCH value, "
                         "run nothing")
    args = ap.parse_args()

    if args.list:
        list_gates()
        return

    from repro.runtime import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (fig09_training_curve, fig10_dgro_vs_ga,
                            fig11_ring_selection, fig12_ring_ablation,
                            fig13_kring_compare, fig14_parallel,
                            fig15_batcheval, fig16_churn, fig17_service,
                            fig18_obs, fig19_routing, fig20_scale,
                            fig21_hier, roofline_table)

    fast = args.fast
    jobs = [
        # the >=10x device-vs-host rollout gate always runs at N=32, E=8;
        # --fast only shrinks the training curve
        ("fig09", lambda: fig09_training_curve.run(
            n=10 if fast else 14, epochs=16 if fast else 120,
            bench_n=32, bench_envs=8)),
        ("fig10", lambda: fig10_dgro_vs_ga.run(
            n=10 if fast else 14, epochs=16 if fast else 50,
            ga_budget=200 if fast else 1000)),
        ("fig11-uniform", lambda: fig11_ring_selection.run(
            "uniform", (30, 60) if fast else (50, 100, 200))),
        ("fig11-gaussian", lambda: fig11_ring_selection.run(
            "gaussian", (30, 60) if fast else (50, 100, 200))),
        ("fig15-fabric", lambda: fig11_ring_selection.run(
            "fabric", (30, 60) if fast else (50, 100, 200))),
        ("fig15-bitnode", lambda: fig11_ring_selection.run(
            "bitnode", (30, 60) if fast else (50, 100, 200))),
        ("fig12", lambda: fig12_ring_ablation.run(
            sizes=(30, 60) if fast else (50, 100, 200))),
        ("fig13", lambda: fig13_kring_compare.run(
            "uniform", (30, 60) if fast else (50, 100, 200),
            ga_budget=100 if fast else 300)),
        ("fig17-bitnode", lambda: fig13_kring_compare.run(
            "bitnode", (30, 60) if fast else (50, 100, 200),
            ga_budget=100 if fast else 300)),
        # the >=5x batched-vs-host construction gate always runs at N=256,
        # M=8, and the <=1.05 diameter-parity gate on uniform+bitnode; --fast
        # only shrinks the M sweep and the seed fleet
        ("fig14", lambda: fig14_parallel.run(
            seeds=(0, 1) if fast else (0, 1, 2),
            partitions=(1, 8, 32) if fast else (1, 2, 4, 8, 16, 32))),
        ("fig15-batcheval", lambda: fig15_batcheval.run(
            bs=(1, 8, 64) if fast else (1, 8, 64, 256),
            ns=(32, 64) if fast else (32, 64, 128, 256),
            scipy_cap=16 if fast else 64)),
        # the >=5x incremental-vs-full gate always runs at N=128; --fast
        # only shrinks the op stream and the trajectory fleets
        ("fig16-churn", lambda: fig16_churn.run(
            gate_ops=40 if fast else 80,
            traj_n0=24 if fast else 48)),
        # the service gate always exercises a live daemon + crash/restart;
        # --fast only shrinks the event stream
        ("fig17-service", lambda: fig17_service.run(
            events=60 if fast else 200,
            n0=64 if fast else 128)),
        # the <=5% instrumentation-overhead gate always runs at N=64 over
        # 240 events (smaller runs finish in ~15ms and timer noise swamps
        # the delta); --fast only trims the repeat count (kept even so the
        # A/B order alternation balances run positions)
        ("fig18-obs", lambda: fig18_obs.run(
            repeats=2 if fast else 4)),
        # the >=5x router gate + host parity + success 1.0 always run at
        # N=256, P=1024; --fast only shrinks the stretch matrix
        ("fig19-routing", lambda: fig19_routing.run(
            matrix_n=64 if fast else 256,
            matrix_pairs=128 if fast else 256)),
        # the parity + memory gates always run at N=256, B<=64; --fast
        # shrinks the scaling sweep, full caps the timed candidates at
        # N>=2048 (the honest B=64 N=4096 cell is the module's __main__)
        ("fig20-scale", lambda: fig20_scale.run(
            ns=(64, 128, 256) if fast else (256, 1024, 4096),
            b=16 if fast else 64,
            b_cap=None if fast else 8)),
        # the hier gates always run at N=1e5 (scale) and N<=512 (bound
        # validity vs exact APSP + flat parity); --fast only trims the
        # churn stream toward the >=200-event floor and the small-N size
        ("fig21-hier", lambda: fig21_hier.run(
            events=200 if fast else 300,
            n_small=256 if fast else 384)),
        ("roofline", roofline_table.run),
    ]

    undeclared = [name for name, _ in jobs if name not in GATES]
    assert not undeclared, f"jobs missing a GATES entry: {undeclared}"

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in jobs:
        gate = GATES[name]
        buf = io.StringIO()
        try:
            if args.verbose:
                res = fn()
            else:
                with contextlib.redirect_stdout(buf):
                    res = fn()
            if gate.passes(res):
                print(f"{res['name']},{res['us_per_call']:.1f},{res['derived']}")
            else:
                failures += 1
                print(f"{res['name']},{res['us_per_call']:.1f},"
                      f"GATE FAILED ({gate.description}): {res['derived']}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,ERROR {e!r}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
