"""Share of the slots the ingress flush scans that land a row: the
program's ``flush.rows`` over ``flush.slots`` (plane length x ingress
block per ``_enqueue_rows`` dispatch), summed over the ``tm.flush`` spans
of the traced part of the window. Layer: ingress flush
(``serve/router.py:_enqueue_rows``)."""
from bench import program


def read(ctx):
    slots = program.counted(ctx, "tm.flush", "flush.slots")
    if not slots:
        return None
    return 100.0 * program.counted(ctx, "tm.flush", "flush.rows") / slots
