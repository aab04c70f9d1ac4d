"""Mean server-side handling time of POST /v1/events in the window, ms
(``repro_http_request_seconds{endpoint="events"}``)."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after,
                           "repro_http_request_seconds", endpoint="events")
    return None if v is None else v * 1e3
