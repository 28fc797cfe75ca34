"""Patch evaluation's share of its roofline: the least time of the rows put
through patch evaluation in the traced window at v5e's int8 peak
(``bench/work_ctm.py``), over the summed device time of the
``patch_clause_eval_replicated`` kernel's events there. The rows: each row
trained in the whole ticks of the traced window (the drain's training
pass), each row of a monitoring pass the program reports
(``patch.evals.monitor``; the catch-up loop asks for none), and the eval
rows of every tenant at each of the program's ``tm.analysis`` spans.
Layer: patch evaluation (``kernels/clause_eval.py``)."""
from bench import program, work_ctm

# the compiled Pallas patch-evaluation kernel's operations in a v5e trace
PATTERN = r"patch_clause_eval_replicated"


def read(ctx):
    c = ctx.counts
    if ctx.trace is None or not c["traced_rows_trained"]:
        return None
    t = ctx.trace.op_seconds(PATTERN)
    analyses = program.spans(ctx, "tm.analysis")
    if t <= 0 or analyses is None:
        return None
    monitored = program.counted(ctx, "tm.drain", "patch.evals.monitor") or 0
    rows = (c["traced_rows_trained"]
            + monitored / work_ctm.n_patches(ctx.conf)
            + len(analyses) * ctx.conf["tenants"]
            * ctx.conf["data"]["eval_rows"])
    return 100.0 * work_ctm.patch_eval_seconds(ctx.conf, rows, ctx.peak) / t
