"""What :class:`~repro.serve.service.TMService` reports about its own work.

One small system, three parts:

* **Device scopes.** The jitted entries of the flush and the drain are
  decorated with ``jax.named_scope(FLUSH | DRAIN)``, so every HLO op they
  compile to carries ``tm.flush`` (the ingress enqueue) or ``tm.drain``
  (the drain step and its key split) in its ``op_name`` metadata, which an
  HLO dump and any profiler that reads that metadata group ops by; a patch
  machine's patch literals and patch evaluation carry ``tm.patch``
  (``core/tm.py``) inside them and inside the analysis. A scope changes
  metadata only, never the computation.
* **Host spans** (``tm.flush``, ``tm.drain``, ``tm.analysis``,
  ``tm.policy``), recorded only while a ``jax.profiler`` trace is
  recording. Each one then also enters ``jax.profiler.TraceAnnotation
  (name)``, which puts it on the profiler's host plane on the device ops'
  clock, and keeps a :class:`Span` (``time.perf_counter`` start and end,
  the innermost ``tm.*`` span open around it, and the service's counter
  increments made while it was open) in one bounded, process-wide list,
  read by :func:`spans`. With no trace recording a span costs one check of
  the profiler's state: no annotation, no clock read, no device sync.
* **Counters**, always on, read as one snapshot by :meth:`Obs.counters`:
  ``flush.rows`` (rows landed in device buffers), ``flush.slots`` (plane
  length x ingress block per enqueue dispatch: the staging slots it
  carries, filled or not), ``residency.activations`` /
  ``residency.evictions``, ``feedback.grid_steps`` (a plain machine's
  drain: grid steps of the Pallas feedback kernel per dispatch, from the
  plane's shapes: one launch per scan step on each device; 0 on the
  ``ref`` backend), ``patch.evals.<pass>`` (a patch machine's row
  slots x patches put through patch evaluation, counted at dispatch from
  the planes' shapes, for the ``train`` and ``monitor`` passes of the
  drain and the ``analysis`` pass), and the process-wide ``jit.traces`` /
  ``jit.compile_s`` (jaxpr traces and backend-compile seconds per
  function name, from ``jax.monitoring``).
  Every jaxpr trace's time is also kept, so :func:`traces_between` counts
  the traces of any stretch of time.

Every span opens inside the service's device lock, so one stack per
service gives the parent of each.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import NamedTuple, Optional

import jax

# device scopes (jax.named_scope), also the names of the matching host spans
FLUSH = "tm.flush"
DRAIN = "tm.drain"
# host-only spans: analysis is a chain of eager dispatches and the policy
# a host FSM with an eager select, so neither has a jitted body to scope
ANALYSIS = "tm.analysis"
POLICY = "tm.policy"
# a patch machine's counter (DESIGN.md §18): row slots x patches put
# through patch evaluation, one key per pass
PATCH_EVALS = "patch.evals"
PATCH_PASSES = ("train", "monitor", "analysis")
# a plain machine's counter: grid steps of the drain's feedback kernel
# (kernels/feedback.py), from the plane's shapes, per drain dispatch
FEEDBACK_GRID_STEPS = "feedback.grid_steps"

# spans and jaxpr-trace times kept in memory; the oldest drop first (a
# traced benchmark window records a few dozen spans a second)
SPAN_LIMIT = 1 << 16
TRACE_LIMIT = 1 << 12

_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start: float               # time.perf_counter() seconds
    end: float
    parent: Optional[str]      # innermost tm.* span open around it
    counts: dict               # counter increments while open (nonzero)


_SPANS: collections.deque = collections.deque(maxlen=SPAN_LIMIT)


def spans(name: Optional[str] = None, t0: float = float("-inf"),
          t1: float = float("inf")) -> list:
    """The recorded spans (called ``name`` when given) that lie wholly
    inside ``[t0, t1]`` on ``time.perf_counter``, oldest first."""
    return [s for s in list(_SPANS)
            if (name is None or s.name == name)
            and s.start >= t0 and s.end <= t1]


def recording() -> bool:
    """Whether a ``jax.profiler`` trace is recording: spans are on."""
    return jax.profiler.TraceAnnotation.is_enabled()


class _JitCounts:
    """Process-wide jaxpr traces and backend-compile seconds by function
    name, and the time of each recent trace; ``jax.monitoring`` holds its
    listeners for the whole process, so this registers once."""

    def __init__(self):
        self.lock = threading.Lock()
        self.traces: dict[str, int] = {}
        self.compile_s: dict[str, float] = {}
        self.times: collections.deque = collections.deque(
            maxlen=TRACE_LIMIT)
        self.registered = False

    def register(self) -> None:
        with self.lock:
            if not self.registered:
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_event)
                self.registered = True

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event not in (_JAXPR_TRACE, _BACKEND_COMPILE):
            return
        t = time.perf_counter()
        name = str(kw.get("fun_name", "?"))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]     # compiles name the module, traces the function
        with self.lock:
            if event == _JAXPR_TRACE:
                self.traces[name] = self.traces.get(name, 0) + 1
                self.times.append((t, name))
            else:
                self.compile_s[name] = self.compile_s.get(name, 0) + duration

    def snapshot(self) -> dict:
        with self.lock:
            return {"jit.traces": dict(self.traces),
                    "jit.compile_s": dict(self.compile_s)}


_JIT = _JitCounts()


def traces_between(t0: float, t1: float) -> dict:
    """Jaxpr traces by function name that ended inside ``[t0, t1]`` on
    ``time.perf_counter`` (of the last ``TRACE_LIMIT`` of the process)."""
    out: dict[str, int] = {}
    with _JIT.lock:
        times = list(_JIT.times)
    for t, name in times:
        if t0 <= t <= t1:
            out[name] = out.get(name, 0) + 1
    return out


class _Open:
    __slots__ = ("obs", "name", "parent", "ann", "t", "c0")

    def __init__(self, obs: "Obs", name: str):
        self.obs, self.name = obs, name

    def __enter__(self):
        stack = self.obs._stack
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.c0 = dict(self.obs._counts)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.obs._stack.pop()
        counts = {k: v - self.c0[k] for k, v in self.obs._counts.items()
                  if v != self.c0[k]}
        _SPANS.append(Span(self.name, self.t, t1, self.parent, counts))
        return False


_OFF = contextlib.nullcontext()


class Obs:
    """One service's span stack and counters (see the module docstring)."""

    def __init__(self):
        _JIT.register()
        self._stack: list = []
        self._counts = {"flush.rows": 0, "flush.slots": 0,
                        "residency.activations": 0,
                        "residency.evictions": 0,
                        FEEDBACK_GRID_STEPS: 0,
                        **{f"{PATCH_EVALS}.{p}": 0 for p in PATCH_PASSES}}

    def span(self, name: str):
        """A host span; a no-op unless a profiler trace is recording."""
        if not recording():
            return _OFF
        return _Open(self, name)

    def add(self, counter: str, n) -> None:
        self._counts[counter] += int(n)

    def counters(self) -> dict:
        """One snapshot of every counter: this service's integers and the
        process's ``jit.*`` dicts keyed by function name."""
        return {**self._counts, **_JIT.snapshot()}
