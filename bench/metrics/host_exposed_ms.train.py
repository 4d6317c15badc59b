"""Host work the device waited for, in ms per window epoch, averaged over
the cell's chips: the device's idle time (the window less the union of
its op intervals, as ``idle_share.train`` counts it) that falls under
some ``amb.*`` span and outside every ``amb.epoch.wait``."""
from bench import spans, trace


def read(ctx):
    work = spans.host_work(ctx)
    exposed = [trace.length(spans.intersect(trace.idle_gaps(
        ctx.trace["devices"][d]["ops"], ctx.lo, ctx.hi), work))
        for d in ctx.devices]
    return spans.per_epoch_ms(ctx, sum(exposed) / len(exposed))
