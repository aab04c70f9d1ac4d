"""Mean wait to acquire the ServiceState lock in the window, ms
(``repro_service_lock_wait_seconds``)."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after,
                           "repro_service_lock_wait_seconds")
    return None if v is None else v * 1e3
