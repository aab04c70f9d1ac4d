"""A run with the timed path broken underneath comes out not correct, and
so does a run with the control (the reference one precision down) in the
program's place.

Each test drives a whole run of ``bitcoin4k.churn`` on the CPU at a small
fleet, past the harness's look for a chip: boot, warm-up, window, read-back
and every check.  Run with ``pytest chipbench/tests`` (a few minutes).
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import run  # noqa: E402

CELL, N0, SECONDS = "bitcoin4k.churn", 128, 2.0


def _run(seed, patch=None, control=None):
    return run.run_cell(ROOT, CELL, seed, SECONDS, False, require_chip=False,
                        n0=N0, patch=patch, control=control)


def _failing(res):
    return sorted(k for k, c in res["checks"].items()
                  if not c["value"] <= c["limit"])


def test_sound_run_is_correct_and_its_control_is_not():
    res = _run(2**31 + 9, control="bfloat16")
    assert all(c["value"] <= c["limit"]
               for c in res["program_checks"].values()), res["program_checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert not res["correct"]
    assert _failing(res)


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.service.state import ServiceState

    def ingest(self, events):
        with self.lock:                   # acknowledges, applies nothing
            self.events_ingested += len(events)
            return {"accepted": len(events), "applied": 0}

    res = _run(31, patch=lambda: monkeypatch.setattr(
        ServiceState, "ingest", ingest))
    assert not res["correct"]
    assert "live_set_mismatch" in _failing(res)


def test_half_of_each_batch_left_out_is_caught(monkeypatch):
    from repro.service.state import ServiceState
    inner = ServiceState.ingest

    def ingest(self, events):               # keeps the first half, rounded
        keep = len(events) // 2             # down: a lone event is dropped
        out = inner(self, events[:keep])
        self.events_ingested += len(events) - keep
        return {**out, "accepted": len(events)}

    res = _run(32, patch=lambda: monkeypatch.setattr(
        ServiceState, "ingest", ingest))
    assert not res["correct"]
    assert "live_set_mismatch" in _failing(res)


@pytest.mark.parametrize("where", ["route", "diameter"])
def test_answer_altered_where_produced_is_caught(monkeypatch, where):
    from repro.service.state import ServiceState
    inner = getattr(ServiceState, where)
    key = "distance" if where == "route" else "diameter"

    def altered(self, *a, **kw):
        out = inner(self, *a, **kw)
        if out.get(key) is not None:
            out[key] = out[key] * 1.001
        return out

    res = _run(33, patch=lambda: monkeypatch.setattr(
        ServiceState, where, altered))
    assert not res["correct"]
    want = {"route": {"window_route_gap", "route_gap", "exact_route_gap"},
            "diameter": {"diameter_gap"}}[where]
    assert want <= set(_failing(res))
