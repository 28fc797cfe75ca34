"""Device time of the ingress flush per traced tick: the union of the
device operations that ran while the program's ``tm.flush`` span was open,
inside the ``bench.tick`` spans that lie wholly in the traced window, over
their number (``bench/program.py``). Layer: ingress flush
(``serve/router.py:_enqueue_rows``)."""
from bench import program


def read(ctx):
    return program.device_ms_per_tick(ctx, "tm.flush")
