"""What the program reports about its own work, read after the window.

``repro.serve.obs`` keeps the service's host spans (``tm.flush``,
``tm.drain``, ``tm.analysis``, ``tm.policy``: recorded while a profiler
trace records, so in the traced part of the window), the counter increments
made inside each span, and the time of every jaxpr trace. The readers of
``bench/metrics`` read them here. A program without that module reports
nothing: every function here then returns None, and the metric is left out
of the line.

A v5e trace's device operations carry no ``op_name``, so a scope's device
time is read through the program's host span of the same name: the device
time that ran while that span was open. The flush and the drain each end in
a host read of what their dispatches returned, so their spans hold their
device work. The host spans are on ``time.perf_counter``; the trace's own
clock is mapped onto it by the ``bench.tick`` spans, which the harness
records on both.
"""
from __future__ import annotations

from bench.trace import merged

# how far a tick's bound may lie off the line that maps one clock on the other
CLOCK_SLACK_S = 1e-3


def obs():
    """The program's ``repro.serve.obs`` module, or None."""
    try:
        from repro.serve import obs
    except ImportError:
        return None
    return obs


def spans(ctx, name: str):
    """The program's ``name`` spans that lie wholly in the window, or None
    when the program has no ``repro.serve.obs``."""
    o = obs()
    if o is None:
        return None
    return o.spans(name, ctx.t_open, ctx.t_close)


def counted(ctx, name: str, counter: str):
    """The sum of ``counter``'s increments inside the window's ``name``
    spans, or None without such a span."""
    sp = spans(ctx, name)
    if not sp:
        return None
    return sum(s.counts.get(counter, 0) for s in sp)


def traces(ctx):
    """Jaxpr traces inside the window, or None when the program keeps no
    count of them."""
    o = obs()
    if o is None:
        return None
    return sum(o.traces_between(ctx.t_open, ctx.t_close).values())


def clock(ctx):
    """A map from ``perf_counter`` seconds to trace nanoseconds, a line
    fitted on the traced ticks' bounds (the first ticks of the window, on
    both clocks), or None when a bound lies more than ``CLOCK_SLACK_S``
    off the line or the two clocks' rates differ by more than 0.1%."""
    host = sorted(ctx.spans.get("bench.tick", []))
    trace = sorted(ctx.trace.host.get("bench.tick", []))
    if not host or not trace:
        return None
    t0, h0 = trace[0][0], host[0][0]
    pts = [(h - h0, (t - t0) * 1e-9)
           for (ta, tb), (ha, hb) in zip(trace, host)
           for h, t in ((ha, ta), (hb, tb))]
    mx = sum(x for x, _ in pts) / len(pts)
    my = sum(y for _, y in pts) / len(pts)
    var = sum((x - mx) ** 2 for x, _ in pts)
    if var <= 0:
        return None
    rate = sum((x - mx) * (y - my) for x, y in pts) / var
    if abs(rate - 1.0) > 1e-3 or max(
            abs(y - my - rate * (x - mx)) for x, y in pts) > CLOCK_SLACK_S:
        return None
    return lambda h: t0 + round((my + rate * (h - h0 - mx)) * 1e9)


def _intersect(xs: list, ys: list) -> list:
    """The intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def device_ms_per_tick(ctx, name: str):
    """Device milliseconds that ran while a ``name`` span was open, inside
    the ``bench.tick`` spans that lie wholly in the traced window, per such
    tick, averaged over chips: the union of the operations' intervals, so a
    loop and the operations of its body count once. None without device
    operations, spans, whole ticks or an agreeing clock."""
    red = ctx.trace
    if red is None or not red.ops:
        return None
    sp = spans(ctx, name)
    if not sp:
        return None
    to_trace = clock(ctx)
    if to_trace is None:
        return None
    ticks = merged((a, b) for a, b in red.host.get("bench.tick", [])
                   if a >= red.t0 and b <= red.t1)
    if not ticks:
        return None
    opened = _intersect(
        merged((to_trace(s.start), to_trace(s.end)) for s in sp), ticks)
    total = sum(b - a for evs in red.ops
                for a, b in _intersect(merged((a, b) for _, a, b, _ in evs),
                                       opened))
    return total * 1e-9 / len(red.ops) / len(ticks) * 1e3
