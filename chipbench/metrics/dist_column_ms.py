"""Mean time of one read of a column of the distance matrix from the
device to the host in the window, ms
(``repro_span_seconds{span="dist.column"}``): the read that serves a
flat route.  None where no read was made, or the program has no such
span."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after, "repro_span_seconds",
                           span="dist.column")
    return None if v is None else v * 1e3
