"""Full rebuilds per thousand events processed in the window (the
``/v1/stats`` ``maintenance.rebuilds`` and ``events_processed`` deltas)."""


def read(ctx):
    events = (ctx.stats_after["events_processed"]
              - ctx.stats_before["events_processed"])
    if events <= 0:
        return None
    rebuilds = (ctx.stats_after["maintenance"]["rebuilds"]
                - ctx.stats_before["maintenance"]["rebuilds"])
    return 1e3 * rebuilds / events
