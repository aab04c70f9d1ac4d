"""Mean wall time of the re-optimizer's optimize phase in the window, s
(``repro_span_seconds{span="reopt.optimize"}``)."""
from chipbench import scrape


def read(ctx):
    return scrape.window_mean(ctx.before, ctx.after, "repro_span_seconds",
                              span="reopt.optimize")
