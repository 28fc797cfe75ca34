"""Patch evaluations per trained row: the program's ``patch.evals``
increments (row slots x patches put through patch evaluation, counted at
dispatch: the drain's ``train`` and ``monitor`` passes inside its
``tm.drain`` spans, the ``analysis`` pass inside ``tm.analysis``) in the
traced part of the window, over the rows trained in its whole ticks.
None for a program or a machine that counts none. Layer: patch
evaluation (``core/tm.py``, ``kernels/clause_eval.py``)."""
from bench import program

PASSES = (("tm.drain", "train"), ("tm.drain", "monitor"),
          ("tm.analysis", "analysis"))


def read(ctx):
    rows = ctx.counts["traced_rows_trained"]
    if not rows:
        return None
    n = sum(program.counted(ctx, span, f"patch.evals.{p}") or 0
            for span, p in PASSES)
    return n / rows if n else None
