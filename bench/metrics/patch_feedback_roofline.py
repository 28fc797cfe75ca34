"""The patch feedback kernel's share of its roofline: the least time of
the feedback of the tenants trained in the whole ticks of the traced window
at peak HBM bandwidth (``bench/work_ctm.py``: each one's TA bank read and
written once a tick), over the summed device time of the
``feedback_patch_replicated`` kernel's events there. Layer: patch feedback
(``kernels/feedback.py``)."""
from bench import work_ctm

# the compiled Pallas patch feedback kernel's operations in a v5e trace
PATTERN = r"feedback_patch_replicated"


def read(ctx):
    c = ctx.counts
    if ctx.trace is None or not c["traced_tenant_ticks"]:
        return None
    t = ctx.trace.op_seconds(PATTERN)
    if t <= 0:
        return None
    return 100.0 * work_ctm.feedback_seconds(
        ctx.conf, c["traced_tenant_ticks"], ctx.peak) / t
