"""The readers of the lock, host-copy, rebuild-copy and churn-engine
metrics on two recorded ``/v1/metrics`` scrapes (a CPU window of 20
events and 50 routes), and on the same scrapes without the series that
an older program lacks.  Run with ``pytest chipbench/tests``."""
from __future__ import annotations

import os
import re
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import scrape  # noqa: E402
from chipbench.run import load_reader  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")

# each reader's value on the recorded window, from the numbers in the files
EXPECTED = {
    "lock_hold_ms.route":
        (0.004799309000645735 - 0.0003174889999399966) / 50 * 1e3,
    "lock_hold_ms.ingest":
        (0.3915924500001893 - 0.22788205499989544) / 10 * 1e3,
    "dist_to_host_ms":
        (0.0001608480000641066 - 3.95280000020648e-05) / 10 * 1e3,
    "dist_copies_per_kroute": 1e3 * 10 / 50,
    "rebuild_copy_ms":
        ((0.005146711000065807 - 0.00280575699991914)
         + (0.0002697039999475237 - 0.00013137799999185518)) / 4 * 1e3,
    "engine_host_ms": (0.2508766809997951 - 0.09784171699993749) / 20 * 1e3,
}

# what the new metrics read, and an older program does not export
_NEW_SERIES = re.compile(
    r'^repro_(service_lock_hold_seconds|dist_host_\w+_total)'
    r'|span="(dist\.to_host|rebuild\.\w+|engine\.policy|state\.\w+)"')


def _text(name: str) -> str:
    with open(os.path.join(DATA, name)) as f:
        return f.read()


def _ctx(before: str, after: str) -> SimpleNamespace:
    return SimpleNamespace(before=scrape.parse(before),
                           after=scrape.parse(after))


def _without_new_series(text: str) -> str:
    return "\n".join(line for line in text.splitlines()
                     if not _NEW_SERIES.search(line))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_recorded_window(name):
    ctx = _ctx(_text("scrape_before.txt"), _text("scrape_after.txt"))
    assert load_reader(ROOT, name)(ctx) == pytest.approx(EXPECTED[name],
                                                         rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_an_older_program(name):
    ctx = _ctx(_without_new_series(_text("scrape_before.txt")),
               _without_new_series(_text("scrape_after.txt")))
    assert load_reader(ROOT, name)(ctx) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_an_empty_window(name):
    after = _text("scrape_after.txt")
    assert load_reader(ROOT, name)(_ctx(after, after)) is None
