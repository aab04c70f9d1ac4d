"""Mean time one route holds the ServiceState lock in the window, ms
(``repro_service_lock_hold_seconds{holder="state.route"}``)."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after,
                           "repro_service_lock_hold_seconds",
                           holder="state.route")
    return None if v is None else v * 1e3
