"""Device time of the drain per traced tick: the union of the device
operations that ran while the program's ``tm.drain`` span was open (the
drain step and its key split), inside the ``bench.tick`` spans that lie
wholly in the traced window, over their number (``bench/program.py``).
Layer: drain model step (``core/online.py:_consume_many_replicated``)."""
from bench import program


def read(ctx):
    return program.device_ms_per_tick(ctx, "tm.drain")
