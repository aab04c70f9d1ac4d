"""Mean wall time of one full APSP rebuild of the incremental distance
matrix in the window, ms (``repro_jit_execute_seconds{fn=
"incremental.rebuild"}``, which waits for the result)."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after, "repro_jit_execute_seconds",
                           fn="incremental.rebuild")
    return None if v is None else v * 1e3
