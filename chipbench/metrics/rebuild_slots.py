"""Slots per rebuild in the window: the Floyd–Warshall's size, the live
count padded to a bucket of 256 or the whole capacity
(``repro_rebuild_slots_total`` over the rebuilds counted by
``repro_jit_compile_seconds`` and ``repro_jit_execute_seconds``
``{fn="incremental.rebuild"}``, all deltas: the first rebuild at a size is
timed as a compile).  None where the program does not count the slots, or
no rebuild ran."""
from chipbench import scrape

SLOTS = "repro_rebuild_slots_total"
TIMED = ("repro_jit_compile_seconds_count", "repro_jit_execute_seconds_count")


def read(ctx):
    if SLOTS not in ctx.before or SLOTS not in ctx.after:
        return None
    rebuilds = sum(scrape.total(ctx.after, name, fn="incremental.rebuild")
                   - scrape.total(ctx.before, name, fn="incremental.rebuild")
                   for name in TIMED)
    if rebuilds <= 0:
        return None
    return (scrape.total(ctx.after, SLOTS)
            - scrape.total(ctx.before, SLOTS)) / rebuilds
