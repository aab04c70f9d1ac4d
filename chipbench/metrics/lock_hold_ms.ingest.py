"""Mean time one ingest holds the ServiceState lock in the window, ms
(``repro_service_lock_hold_seconds{holder="state.ingest"}``): the routes
that arrive meanwhile queue behind it."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after,
                           "repro_service_lock_hold_seconds",
                           holder="state.ingest")
    return None if v is None else v * 1e3
