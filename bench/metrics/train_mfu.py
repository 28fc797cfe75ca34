"""The drain step's share of the chips' peak: the least time the TM work
trained in the traced window needs at peak HBM bandwidth (``bench/work.py``: each
tenant trained in a tick reads and writes its int8 TA bank once, plus its
packed rows), over traced window time x chips. Bytes-bound: v5e publishes no
vector-unit rate. Layer: drain model step
(``core/online.py:_consume_many_replicated``)."""


def read(ctx):
    c = ctx.counts
    if not c["traced_tenant_ticks"] or ctx.trace is None:
        return None
    least = ctx.work.least_seconds(ctx.work.train_bytes(
        ctx.conf, c["traced_tenant_ticks"], c["traced_rows_trained"]),
        ctx.peak)
    return 100.0 * least / (ctx.trace.window_s * ctx.chips)
