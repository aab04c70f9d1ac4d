"""Host-device copy time per full rebuild in the window, ms: the sums of
``repro_span_seconds{span="rebuild.to_device"}`` (the adjacency's upload
and the result's re-upload) and ``{span="rebuild.to_host"}`` (the
result's download), over the rebuilds counted by
``repro_jit_execute_seconds{fn="incremental.rebuild"}``, all deltas.
None where the program has no such spans."""
from chipbench import scrape

PHASES = ("rebuild.to_device", "rebuild.to_host")


def _delta(ctx, series, **match):
    return (scrape.total(ctx.after, series, **match)
            - scrape.total(ctx.before, series, **match))


def read(ctx):
    spans = {dict(labels).get("span")
             for labels in ctx.after.get("repro_span_seconds_sum", {})}
    if not spans.issuperset(PHASES):
        return None
    rebuilds = _delta(ctx, "repro_jit_execute_seconds_count",
                      fn="incremental.rebuild")
    if rebuilds <= 0:
        return None
    copy_s = sum(_delta(ctx, "repro_span_seconds_sum", span=p)
                 for p in PHASES)
    return copy_s / rebuilds * 1e3
