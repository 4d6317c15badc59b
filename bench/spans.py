"""The program's own host spans in the traced window.

The program names its epoch loop and data plane in the profiler's trace
(``jax.profiler.TraceAnnotation``, on the clock of the device ops):
``amb.epoch`` with its phases ``amb.epoch.clock``, ``.dispatch``,
``.wait`` (the one blocking read of the step's results) and ``.record``,
the caller's ``amb.on_step``, and the data plane's ``amb.data.wait``,
``.build`` and ``.put``.  A program without them reads as no epoch, and
the metrics built here read nothing.
"""
from __future__ import annotations

from bench import trace

EPOCH = "amb.epoch"
EPOCH_WAIT = "amb.epoch.wait"
DATA_WAIT = "amb.data.wait"
PREFIX = "amb."


def merged(ctx, keep) -> list:
    """Merged intervals, clipped to the window, of the host spans whose
    name passes ``keep``."""
    return trace.clip(trace.union(trace.spans(
        e for e in ctx.trace["host"] if keep(e[0]))), ctx.lo, ctx.hi)


def intersect(a, b) -> list:
    """Parts of the merged intervals ``a`` that ``b`` covers."""
    return trace.subtract(a, trace.subtract(a, b))


def host_work(ctx) -> list:
    """The program's host time outside its wait for the step's results:
    the union of every ``amb.*`` span less the union of
    ``amb.epoch.wait``."""
    return trace.subtract(merged(ctx, lambda n: n.startswith(PREFIX)),
                          merged(ctx, EPOCH_WAIT.__eq__))


def per_epoch_ms(ctx, ns: float):
    """``ns`` of the window in ms per epoch, or None when the window
    holds no ``amb.epoch`` span."""
    if ctx.epochs <= 0 or not merged(ctx, EPOCH.__eq__):
        return None
    return ns * 1e-6 / ctx.epochs
