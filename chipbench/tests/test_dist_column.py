"""The ``dist_column_ms`` reader on two recorded ``/v1/metrics`` scrapes
of the distance-matrix series (a CPU window at capacity 48: 8 events and
40 routes, each route one column read), on the same scrapes without the
span that an older program lacks, and on an empty window.  Run with
``pytest chipbench/tests``."""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import scrape  # noqa: E402
from chipbench.run import load_reader  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")


def _text(name: str) -> str:
    with open(os.path.join(DATA, name)) as f:
        return f.read()


def _read(before: str, after: str):
    ctx = SimpleNamespace(before=scrape.parse(before),
                          after=scrape.parse(after))
    return load_reader(ROOT, "dist_column_ms")(ctx)


def test_dist_column_ms_on_recorded_window():
    got = _read(_text("scrape_column_before.txt"),
                _text("scrape_column_after.txt"))
    # span dist.column: sum 0.000423... -> 0.001326..., count 5 -> 45
    want = (0.0013262649954413064 - 0.00042303800000809133) / 40 * 1e3
    assert got == pytest.approx(want, rel=1e-12)


def test_dist_column_ms_reads_nothing_from_an_older_program():
    def without(text):
        return "\n".join(line for line in text.splitlines()
                         if 'span="dist.column"' not in line)
    assert _read(without(_text("scrape_column_before.txt")),
                 without(_text("scrape_column_after.txt"))) is None


def test_dist_column_ms_reads_nothing_from_an_empty_window():
    after = _text("scrape_column_after.txt")
    assert _read(after, after) is None
