"""The feedback kernel's share of its roofline: the least time of the TM
work trained in the traced window at peak HBM bandwidth (as ``train_mfu``),
over the summed device time of the feedback kernel's trace events there.
Layer: feedback kernel (``kernels/feedback.py``)."""

# the compiled Pallas feedback kernel's operations in a v5e trace
PATTERN = r"feedback_plane_replicated"


def read(ctx):
    c = ctx.counts
    if ctx.trace is None or not c["traced_tenant_ticks"]:
        return None
    t = ctx.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    least = ctx.work.least_seconds(ctx.work.train_bytes(
        ctx.conf, c["traced_tenant_ticks"], c["traced_rows_trained"]),
        ctx.peak)
    return 100.0 * least / t
