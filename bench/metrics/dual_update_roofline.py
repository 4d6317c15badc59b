"""Share of its roofline the fused dual-averaging prox reaches (%).

Least time: the bytes the update needs (read z and w0, write w, f32, at
the leaves' real sizes, once per leaf and epoch) over the HBM peak; it is
memory-bound.  Divided by the device time of the kernel's own calls in
the window: the Pallas custom calls, which XLA names after the jitted
``dual_update_pallas``; the pads before them and the casts after them
are not the kernel."""
from bench import counts, trace

KERNEL = "dual_update"


def _is_kernel(op) -> bool:
    return (trace.opcode(op[0]) == "custom-call"
            and KERNEL in trace.short_name(op[0]))


def read(ctx):
    ops = [op for dev in ctx.devices
           for op in ctx.trace["devices"][dev]["ops"] if _is_kernel(op)]
    kernel_ns = trace.op_time_ns(ops, ctx.lo, ctx.hi)
    if kernel_ns <= 0:
        return None
    need = ctx.epochs * counts.dual_update_bytes(
        counts.param_leaf_sizes(ctx.config))
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / (kernel_ns * 1e-9)
