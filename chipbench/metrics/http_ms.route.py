"""Mean server-side handling time of GET /v1/route in the window, ms
(``repro_http_request_seconds{endpoint="route"}``)."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after,
                           "repro_http_request_seconds", endpoint="route")
    return None if v is None else v * 1e3
