"""The harness finds every part of a cell by name, and the generator gives
every seed the same amount of work.  Run with ``pytest chipbench/tests``."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import gen, latency  # noqa: E402
from chipbench.run import load_cell, load_reader  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    parts = load_cell(ROOT, workload)
    assert parts.config["name"] == parts.cell["config"]
    assert {m["name"] for m in parts.end_to_end} >= {"setup_s"}
    assert parts.per_layer
    for m in parts.per_layer:
        assert callable(load_reader(ROOT, m["name"]))


def test_every_metric_has_a_reader_and_every_config_a_file():
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                           m["name"] + ".py")), m["name"]
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    with pytest.raises(KeyError):
        load_cell(ROOT, "no.such.cell")


@pytest.mark.parametrize("workload", CELLS)
def test_seeds_share_the_amount_of_work(workload):
    parts = load_cell(ROOT, workload)
    a = gen.build_schedule(parts.config, parts.traffic, 2**31 + 5, 30)
    b = gen.build_schedule(parts.config, parts.traffic, 2**31 + 5, 30)
    c = gen.build_schedule(parts.config, parts.traffic, 17, 30)
    assert a == b
    assert a["events"] != c["events"]
    for s in (a, c):
        assert [e[0] for e in s["events"]] == sorted(e[0] for e in s["events"])
    kinds = lambda s: sorted(e[1] for e in s["events"])  # noqa: E731
    assert kinds(a) == kinds(c)
    assert len(a["routes"]) == len(c["routes"])


def test_pairs_are_live_and_distinct():
    live = list(range(0, 40, 2))
    for i in range(200):
        src, dst = gen.pick_pair(live, (i * 0.37) % 1, (i * 0.61) % 1)
        assert src != dst and src in live and dst in live


def test_latency_copy_matches_the_program():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.topology import make_latency
    assert (latency.latency_matrix("bitnode", 96, 11)
            == make_latency("bitnode", 96, seed=11)).all()


def test_load_generator_imports_neither_jax_nor_repro():
    code = ("import sys, runpy; sys.argv = ['x']; "
            f"sys.path.insert(0, {ROOT!r}); import chipbench.loadgen; "
            "assert not {'jax', 'repro'} & set(sys.modules), sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
