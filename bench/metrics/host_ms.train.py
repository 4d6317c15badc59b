"""The session's host work on each epoch, in ms per window epoch: the
union of ``amb.epoch`` spans less the union of ``amb.epoch.wait``, the
one blocking read of the step's results."""
from bench import spans, trace


def read(ctx):
    work = trace.subtract(spans.merged(ctx, spans.EPOCH.__eq__),
                          spans.merged(ctx, spans.EPOCH_WAIT.__eq__))
    return spans.per_epoch_ms(ctx, trace.length(work))
