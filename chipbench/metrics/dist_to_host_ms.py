"""Mean time of one copy of the distance matrix from the device to the
host in the window, ms (``repro_span_seconds{span="dist.to_host"}``)."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after, "repro_span_seconds",
                           span="dist.to_host")
    return None if v is None else v * 1e3
