"""The ``rebuild_slots`` reader on two recorded ``/v1/metrics`` scrapes of
the rebuild's series (a CPU run at capacity 640: three rebuilds, at 241
live slots and then 261, so sizes 256, 512 and 512), on the same scrapes
without the counter that an older program lacks, and on an empty window.
Run with ``pytest chipbench/tests``."""
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


def _ctx(before: str, after: str) -> SimpleNamespace:
    return SimpleNamespace(before=scrape.parse(before),
                           after=scrape.parse(after))


def _read(before: str, after: str):
    return load_reader(ROOT, "rebuild_slots")(_ctx(before, after))


def test_rebuild_slots_on_recorded_window():
    got = _read(_text("scrape_rebuild_before.txt"),
                _text("scrape_rebuild_after.txt"))
    # repro_rebuild_slots_total 512 -> 1792 over rebuild counts 1 -> 4
    assert got == pytest.approx((1792 - 512) / (4 - 1), rel=1e-12)


def test_rebuild_slots_counts_a_rebuild_timed_as_a_compile():
    """A rebuild at a size not run before lands in the compile histogram;
    it still counts as one of the window's rebuilds."""
    compiled = 'repro_jit_compile_seconds_count{fn="incremental.rebuild"} '
    got = _read(_text("scrape_rebuild_before.txt") + compiled + "1\n",
                _text("scrape_rebuild_after.txt").replace(
                    "repro_rebuild_slots_total 1792",
                    "repro_rebuild_slots_total 2048") + compiled + "2\n")
    assert got == pytest.approx((2048 - 512) / (4 - 1 + 1), rel=1e-12)


def test_rebuild_slots_reads_nothing_from_an_older_program():
    def without(text):
        return "\n".join(line for line in text.splitlines()
                         if "repro_rebuild_" not in line)
    assert _read(without(_text("scrape_rebuild_before.txt")),
                 without(_text("scrape_rebuild_after.txt"))) is None


def test_rebuild_slots_reads_nothing_from_an_empty_window():
    after = _text("scrape_rebuild_after.txt")
    assert _read(after, after) is None
