"""Copies of the distance matrix from the device to the host per thousand
routes served in the window (``repro_dist_host_copies_total`` over
``repro_service_queries_total{kind="route"}``, both deltas).  None where
the program does not count the copies."""
from chipbench import scrape

COPIES = "repro_dist_host_copies_total"


def read(ctx):
    if COPIES not in ctx.before or COPIES not in ctx.after:
        return None
    routes = (scrape.total(ctx.after, "repro_service_queries_total",
                           kind="route")
              - scrape.total(ctx.before, "repro_service_queries_total",
                             kind="route"))
    if routes <= 0:
        return None
    copies = scrape.total(ctx.after, COPIES) - scrape.total(ctx.before, COPIES)
    return 1e3 * copies / routes
