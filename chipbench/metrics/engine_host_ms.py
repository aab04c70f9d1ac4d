"""Mean host time of the churn engine's work per join, leave or fail in
the window, ms (``repro_span_seconds{span="engine.policy"}``): the
policy's attach, detach and repairs, its adaptation, SWIM detection."""
from chipbench import scrape


def read(ctx):
    v = scrape.window_mean(ctx.before, ctx.after, "repro_span_seconds",
                           span="engine.policy")
    return None if v is None else v * 1e3
