"""The one traffic generator: drives ``TMService`` from a mix's data file.

The mix's ``loop`` key names the loop; ``closed`` (catch-up) is the one
there is. Before every tick, every tenant whose ``buffered`` is below
capacity gets the next row of its own stream, one ``submit_rows`` call per
pass, until every buffer is full (a refused row is offered again on the
next pass); then the consumer ticks. Producer and consumer take turns on
one thread, so each tick finds the same backlog and flushes one staged
block: the work of a tick does not hang on how two threads interleave.

Each call is its own call into the service's public surface: nothing here
batches rows or does the program's work. Every call is a host span
(``bench.submit``, ``bench.tick``), also written into the profiler's trace
when one is recording.
"""
from __future__ import annotations

import time

import numpy as np


class Spans:
    """Host spans of the calls into each layer, on ``time.perf_counter``."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.by_name: dict[str, list] = {}
        if annotate:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    def __call__(self, name: str):
        return _Span(self, name)

    def within(self, name: str, t0: float, t1: float) -> list:
        return [(a, b) for a, b in self.by_name.get(name, ())
                if a >= t0 and b <= t1]


class _Span:
    __slots__ = ("spans", "name", "t", "ann")

    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.ann = self.spans._ann(self.name) if self.spans.annotate else None
        if self.ann is not None:
            self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.spans.by_name.setdefault(self.name, []).append((self.t, t1))
        return False


class Tracer:
    """The profiler over the window's first ``seconds`` (all of it when
    None), ending at the first poll after that. Marks ``bench.window.open``
    and ``bench.window.close`` bound the traced window in the trace's own
    clock; ``t0``/``t1`` are the same bounds on the host clock."""

    def __init__(self, directory, seconds):
        self.dir, self.seconds = directory, seconds
        self.t0 = self.t1 = None

    def _mark(self, name: str) -> None:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(name):
            pass

    def open(self) -> None:
        if self.dir is None:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark("bench.window.open")
        self.t0 = time.perf_counter()

    def poll(self) -> None:
        if (self.t0 is not None and self.t1 is None and self.seconds
                and time.perf_counter() - self.t0 >= self.seconds):
            self.close()

    def close(self) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        import jax

        self.t1 = time.perf_counter()
        self._mark("bench.window.close")
        jax.profiler.stop_trace()


class Ticks:
    """The consumer's record: one entry per ``tick()``."""

    def __init__(self, check):
        self.check = np.asarray(check)
        self.start, self.end = [], []
        self.total = []            # rows trained, all tenants
        self.tenants = []          # tenants that trained a row
        self.cols = []             # rows trained by the checked tenants
        self.analysed = []         # whether the tick ran an analysis
        self.acc = []              # checked tenants' accuracies, when it did
        self.done = 0              # ticks finished

    def tick(self, svc, spans) -> None:
        with spans("bench.tick") as sp:
            rep = svc.tick()
        tr = np.asarray(rep.trained)
        self.start.append(sp.t)
        self.end.append(time.perf_counter())
        self.total.append(int(tr.sum()))
        self.tenants.append(int(np.count_nonzero(tr)))
        self.cols.append(tr[self.check].copy())
        self.analysed.append(rep.accuracy is not None)
        if rep.accuracy is not None:
            self.acc.append(np.asarray(rep.accuracy)[self.check].copy())
        self.done += 1

    def schedule(self):
        return list(zip(self.cols, self.analysed))


class ClosedLoop:
    """Catch-up: every tenant's buffer topped up from its own stream before
    each tick."""

    def __init__(self, svc, traffic: dict, pool, seed: int, check):
        K = svc.n_replicas
        self.svc = svc
        self.traffic = traffic
        self.pool_x, self.pool_y = pool
        self.cap = svc.sc.buffer_capacity
        rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC1]))
        self.start = rng.integers(0, len(self.pool_x), K)
        self.pos = np.zeros(K, np.int64)      # rows accepted per tenant
        self.ticks = Ticks(check)

    def rows_of(self, r: int, n: int):
        idx = (self.start[r] + np.arange(n)) % len(self.pool_x)
        return self.pool_x[idx], self.pool_y[idx]

    def _fill(self, spans) -> None:
        """Passes of one row per tenant that has room, until none has."""
        P = len(self.pool_x)
        while True:
            need = self.svc.buffered < self.cap
            if not need.any():
                return
            idx = (self.start + self.pos) % P
            with spans("bench.submit"):
                ok = self.svc.submit_rows(self.pool_x[idx], self.pool_y[idx],
                                          mask=need)
            if not np.any(ok):
                raise RuntimeError("the service refused every row of a pass "
                                   "while buffers had room")
            self.pos += ok

    def _tick(self, spans) -> None:
        self._fill(spans)
        self.ticks.tick(self.svc, spans)

    def run(self, spans, seconds: float, on_open=None, tracer=None) -> dict:
        """Warm up (set-up), then the measured window; returns its record."""
        tk = self.ticks
        warm = self.traffic["warmup_ticks"]
        while tk.done < warm or sum(tk.analysed) < 2:
            self._tick(spans)
        first = tk.done
        if on_open is not None:
            on_open()
        pos_open = int(self.pos.sum())
        t_open = time.perf_counter()
        while True:
            self._tick(spans)
            if tracer is not None:
                tracer.poll()
            if tk.end[-1] - t_open >= seconds:
                break
        t_close = tk.end[-1]
        return {
            "t_open": t_open, "t_close": t_close, "first_tick": first,
            "rows": sum(tk.total[first:]), "ticks": tk.done - first,
            "rows_accepted": int(self.pos.sum()) - pos_open,
            "accepted": self.pos.copy(),
        }
