"""Time the epoch loop waited on the prefetcher's queue for its input, in
ms per window epoch: the union of the program's ``amb.data.wait`` spans.
The first epoch's queue fill is in the window, so it counts."""
from bench import spans, trace


def read(ctx):
    return spans.per_epoch_ms(ctx, trace.length(
        spans.merged(ctx, spans.DATA_WAIT.__eq__)))
