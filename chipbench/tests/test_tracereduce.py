"""The reduction from trace rows to device numbers.  Run with
``pytest chipbench/tests``."""
from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import tracereduce  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "trace_sample.json.gz")


def test_reduction_by_hand():
    ms = 1e6
    kern = ('%closed_call.{} = f32[256,256]{{1,0:T(8,128)}} custom-call('
            'f32[256,256]{{1,0:T(8,128)}} %copy), custom_call_target='
            '"tpu_custom_call"').format
    rows = [
        (DEV, "XLA Modules", "jit_batched_apsp(3)", 1 * ms, 4 * ms),
        (DEV, "XLA Ops", kern(32), 1 * ms, 1 * ms),
        (DEV, "XLA Ops", kern(35), 2.5 * ms, 2 * ms),
        (DEV, "XLA Modules", "jit_batched_diameter(4)", 6 * ms, 2 * ms),
        (DEV, "XLA Ops", kern(35), 6 * ms, 2 * ms),
        (DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
         9 * ms, 0.5 * ms),
        (HOST, "python", "chipbench.ingest", 4 * ms, 2.2 * ms),
        (HOST, "python", "PjitFunction(route)", 8 * ms, 1 * ms),
        (HOST, "python", "ThreadpoolListener::Record", 2 * ms, 0.0),
    ]
    red = tracereduce.reduce(rows, window_s=0.01)
    assert red["busy_s"] == pytest.approx(5.5e-3)
    assert red["window_s"] == 0.01 and red["devices"] == 1
    assert red["device_ops"][0] == [
        "%closed_call.35 custom-call:tpu_custom_call", pytest.approx(4e-3)]
    assert red["device_ops"][-1] == ["%fusion.1 fusion", pytest.approx(5e-4)]
    assert red["idle_gaps"] == [["chipbench.ingest", pytest.approx(1.5e-3)],
                                ["PjitFunction(route)", pytest.approx(1e-3)],
                                ["no host event", pytest.approx(0.5e-3)]]
    # only the kernels inside the rebuild's own program count
    assert red["rebuild_modules"] == 1
    assert red["rebuild_kernel_s"] == pytest.approx(3e-3)


def test_reduction_of_a_recorded_trace():
    """A slice of a traced ``bitcoin4k.churn`` run on a TPU v5e."""
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace slice in tests/data")
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    red = tracereduce.reduce([tuple(r) for r in rec["rows"]],
                             rec["window_s"])
    for key, want in rec["expect"].items():
        assert red[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["rebuild_modules"] >= 1 and red["rebuild_kernel_s"] > 0
