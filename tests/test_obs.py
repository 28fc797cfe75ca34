"""TMService's own spans, counters and device scopes (repro.serve.obs).

Spans are recorded only while a profiler trace records and never change
what the service computes; the counters are always on; the jitted entries
carry their scope in the HLO they lower to.
"""
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TMConfig, init_state
from repro.core import online as online_mod
from repro.serve import AdaptPolicy, ServiceConfig, TMService
from repro.serve import obs as obs_mod
from repro.serve import router as router_mod
from repro.serve import service as service_mod

K, CAP, BLOCK, CHUNK, F = 4, 8, 4, 4, 16

_RNG = np.random.default_rng(3)
EVAL_X = _RNG.random((24, F)) > 0.5
EVAL_Y = _RNG.integers(0, 3, 24)
ROWS_X = _RNG.random((64, K, F)) > 0.5
ROWS_Y = _RNG.integers(0, 3, (64, K))


def _cfg():
    return TMConfig(n_features=F, max_classes=3, max_clauses=16,
                    n_states=16, backend="ref")


def _service(replicas=K, **kw):
    sc = ServiceConfig(
        replicas=replicas, buffer_capacity=CAP, chunk=CHUNK,
        ingress_block=BLOCK, s=3.0, T=15, seed=5,
        policy=AdaptPolicy(analyze_every=CHUNK, rollback_threshold=0.1),
        **kw)
    return TMService(_cfg(), init_state(_cfg()), sc, eval_x=EVAL_X,
                     eval_y=EVAL_Y)


class _Profiler:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts the spans
    entered, and says a trace records while ``on`` is set."""
    on = False
    entered: list = []

    def __init__(self, name):
        self.name = name

    @staticmethod
    def is_enabled():
        return _Profiler.on

    def __enter__(self):
        _Profiler.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def profiler(monkeypatch):
    _Profiler.on = False
    _Profiler.entered = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Profiler)
    monkeypatch.setattr(obs_mod, "_SPANS",
                        collections.deque(maxlen=obs_mod.SPAN_LIMIT))
    return _Profiler


def _submit(svc, i, n=None):
    mask = None
    if n is not None:
        mask = np.zeros(svc.n_replicas, bool)
        mask[:n] = True
    return svc.submit_rows(ROWS_X[i, :svc.n_replicas],
                           ROWS_Y[i, :svc.n_replicas], mask=mask)


def test_spans_off_record_nothing(profiler):
    svc = _service()
    for i in range(3):
        _submit(svc, i)
    rep = svc.tick()
    assert rep.accuracy is None or rep.accuracy.shape == (K,)
    assert not obs_mod.recording()
    assert obs_mod.spans() == []
    assert profiler.entered == []


def test_spans_on_name_each_layer_and_its_parent(profiler):
    svc = _service()
    profiler.on = True
    for i in range(CHUNK):
        _submit(svc, i)
    obs_mod._SPANS.clear()       # the last submit filled the lanes and flushed
    _submit(svc, CHUNK)
    _submit(svc, CHUNK + 1)      # two rows a lane staged for the tick's flush
    profiler.entered.clear()
    rep = svc.tick()
    assert rep.accuracy is not None, "the tick should analyse"
    spans = obs_mod.spans()
    names = [s.name for s in spans]
    assert names == ["tm.flush", "tm.drain", "tm.analysis", "tm.policy"]
    # none runs inside another: the flush ends before the drain starts
    assert all(s.parent is None for s in spans)
    for a, b in zip(spans, spans[1:]):
        assert a.start <= a.end <= b.start <= b.end
    assert profiler.entered == names
    # the flush's span holds the flush's counter increments, no other does
    assert spans[0].counts["flush.rows"] == K * 2
    assert spans[0].counts["flush.slots"] == K * BLOCK
    assert all(not s.counts for s in spans[1:])
    assert [s.name for s in obs_mod.spans("tm.drain")] == ["tm.drain"]
    assert obs_mod.spans(t0=spans[1].start, t1=spans[2].end) == spans[1:3]


def test_span_parent_is_innermost_open_span(profiler):
    obs = obs_mod.Obs()
    profiler.on = True
    with obs.span("tm.drain"):
        with obs.span("tm.flush"):
            obs.add("flush.rows", 3)
        obs.add("flush.rows", 2)
    inner, outer = obs_mod.spans()
    assert (inner.name, inner.parent) == ("tm.flush", "tm.drain")
    assert (outer.name, outer.parent) == ("tm.drain", None)
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert inner.counts == {"flush.rows": 3}
    assert outer.counts == {"flush.rows": 5}


def test_full_lane_flush_inside_submit_is_a_flush_span(profiler):
    svc = _service()
    profiler.on = True
    for i in range(BLOCK - 1):
        _submit(svc, i)
    assert obs_mod.spans() == []
    _submit(svc, BLOCK - 1)          # fills every lane: submit flushes
    assert [s.name for s in obs_mod.spans()] == ["tm.flush"]


def test_span_list_is_bounded(profiler, monkeypatch):
    monkeypatch.setattr(obs_mod, "_SPANS", collections.deque(maxlen=3))
    obs = obs_mod.Obs()
    profiler.on = True
    for _ in range(5):
        with obs.span("tm.flush"):
            pass
    assert len(obs_mod.spans()) == 3


def test_spans_follow_a_real_profiler_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(obs_mod, "_SPANS",
                        collections.deque(maxlen=obs_mod.SPAN_LIMIT))
    svc = _service()
    for i in range(CHUNK):
        _submit(svc, i)
    assert obs_mod.spans() == []
    with jax.profiler.trace(str(tmp_path)):
        assert obs_mod.recording()
        svc.tick()
    names = [s.name for s in obs_mod.spans()]
    assert names[:2] == ["tm.flush", "tm.drain"]
    assert not obs_mod.recording()
    for i in range(CHUNK):
        _submit(svc, i)
    svc.tick()
    assert [s.name for s in obs_mod.spans()] == names


@pytest.mark.parametrize("resident", [None, 2])
def test_flush_counters(resident):
    svc = _service(resident=resident)
    plane = svc.n_resident
    c0 = svc.obs.counters()
    dispatches = landed = 0
    for t in range(3):
        for i in range(3):
            _submit(svc, 3 * t + i, n=3)     # 3 of 4 replicas, 3 rows each
        landed_t = int(svc.flush().sum())
        assert landed_t == 9
        landed += landed_t
        # one dispatch of the whole plane, or under residency one per
        # cohort of R of the 3 lanes holding rows
        dispatches += 1 if resident is None else -(-3 // plane)
        svc.tick()
    c1 = svc.obs.counters()
    assert c1["flush.rows"] - c0["flush.rows"] == landed
    assert c1["flush.slots"] - c0["flush.slots"] == dispatches * plane * BLOCK


def test_residency_counters_cover_repartition():
    svc = _service(resident="auto")
    c0 = svc.obs.counters()
    for i in range(24):
        _submit(svc, i)
        svc.tick()
    c1 = svc.obs.counters()
    assert svc.repartitions > 0, "the plane never re-sized"
    assert c1["residency.activations"] > c0["residency.activations"]
    assert not hasattr(svc._res, "activations")
    assert not hasattr(svc._res, "evictions")


def test_jit_traces_count_new_shapes_only():
    @jax.jit
    def obs_probe_fn(x):
        return x * 2 + 1

    obs = obs_mod.Obs()
    traces = lambda: obs.counters()["jit.traces"].get("obs_probe_fn", 0)  # noqa: E731
    n0 = traces()
    t0 = time.perf_counter()
    obs_probe_fn(jnp.ones(3)).block_until_ready()
    assert traces() == n0 + 1
    t1 = time.perf_counter()
    obs_probe_fn(jnp.ones(3)).block_until_ready()
    assert traces() == n0 + 1
    t2 = time.perf_counter()
    obs_probe_fn(jnp.ones(5)).block_until_ready()
    assert traces() == n0 + 2
    t3 = time.perf_counter()
    assert obs.counters()["jit.compile_s"].get("obs_probe_fn", 0) > 0
    # each trace is kept with its time
    assert obs_mod.traces_between(t0, t1).get("obs_probe_fn") == 1
    assert "obs_probe_fn" not in obs_mod.traces_between(t1, t2)
    assert obs_mod.traces_between(t0, t3).get("obs_probe_fn") == 2


@pytest.mark.parametrize("resident", [None, 2])
def test_spans_change_nothing_computed(profiler, resident):
    reports = {}
    svcs = {}
    recorded = {}
    for tracing in (False, True):
        profiler.on = tracing
        n0 = len(obs_mod.spans())
        svc = _service(resident=resident)
        reps = []
        for i in range(12):
            _submit(svc, i)
            if i % 2:
                reps.append(svc.tick())
        reports[tracing], svcs[tracing] = reps, svc
        recorded[tracing] = len(obs_mod.spans()) - n0
    profiler.on = False
    assert recorded[True] > 0
    assert recorded[False] == 0
    off, on = svcs[False], svcs[True]
    assert np.array_equal(np.asarray(off.ss.tm.ta_state),
                          np.asarray(on.ss.tm.ta_state))
    assert np.array_equal(off.rng_keys, on.rng_keys)
    for a, b in zip(reports[False], reports[True]):
        assert np.array_equal(a.trained, b.trained)
        assert np.array_equal(a.rolled_back, b.rolled_back)
        assert (a.accuracy is None) == (b.accuracy is None)
        if a.accuracy is not None:
            assert np.array_equal(a.accuracy, b.accuracy)


def _lowered(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).as_text(debug_info=True)


def test_jitted_entries_carry_their_scope():
    svc = _service()
    ss, keys = svc._ss, svc._keys
    xs = np.zeros((K, BLOCK, F), bool)
    ys = np.zeros((K, BLOCK), np.int32)
    counts = np.zeros(K, np.int32)
    assert obs_mod.FLUSH in _lowered(router_mod._enqueue_rows, ss, BLOCK,
                                     xs, ys, counts)
    active = jnp.ones((K,), bool)
    assert obs_mod.DRAIN in _lowered(service_mod._advance_keys, keys, active)
    want = jnp.full((K,), CHUNK, jnp.int32)
    assert obs_mod.DRAIN in _lowered(
        online_mod._consume_many_replicated, svc.cfg, CHUNK, ss, svc.rt,
        want, keys, monitor=False)
    ss1 = jax.tree.map(lambda a: a[0], ss)
    assert obs_mod.DRAIN in _lowered(
        online_mod._consume_many, svc.cfg, CHUNK, ss1, svc.rt,
        jnp.int32(CHUNK), keys[0], monitor=False)
    assert obs_mod.FLUSH in _lowered(
        service_mod._activate_enqueue_rows, ss, keys, BLOCK,
        np.zeros(K, bool), ss, keys, xs, ys, counts)
