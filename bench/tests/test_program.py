"""The readers of the program's own spans and counters (``bench/program.py``
and the five readers over it), on synthetic traces and spans, and in a
traced run on the CPU."""
import collections
import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

from bench import program
from bench.tests.test_trace import PLANES, ev, plane
from bench.tests.tiny import cpu_run, named  # noqa: F401
from bench.trace import Reduction
from repro.serve import obs as obs_mod

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("flush_device_ms.catchup", "drain_device_ms.catchup",
       "analysis_ms.catchup", "flush_slot_use.catchup",
       "window_compiles.catchup")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name.replace('.', '_')}",
        os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(red, spans, t_open=0.0, t_close=100.0):
    from bench import peaks, setup, work

    return NS(conf=setup.load_config("tm-iris-paper-k4096"),
              chips=red.chips if red else 1, trace=red,
              peak=peaks.peaks("TPU v5 lite"), work=work,
              t_open=t_open, t_close=t_close, spans=spans,
              counts={"rows_accepted": 4000, "traced_tenant_ticks": 8192,
                      "traced_rows_trained": 131072})


@pytest.fixture
def recorded(monkeypatch):
    """The program's span list, empty, for a test to fill."""
    spans = collections.deque(maxlen=obs_mod.SPAN_LIMIT)
    monkeypatch.setattr(obs_mod, "_SPANS", spans)
    return spans


def span(name, a, b, parent=None, **counts):
    return obs_mod.Span(name, a, b, parent,
                        {k.replace("_", "."): v for k, v in counts.items()})


# The trace clock runs 5 s (5e9 ns) ahead of the host's perf_counter, in
# ns; the window is 5e9..5e9+2000 ns. Two whole ticks and a third cut by the
# window's end. Tick 1: the flush (an outer while holding two nested ones),
# the drain (a while holding a fusion), an analysis op. Tick 2: a flush op,
# the key split and the drain. Tick 3: a flush op the window cuts.
T = 5_000_000_000


def _host(a_ns):
    return a_ns * 1e-9


TICKS = [(0, 1000), (1100, 1900), (1950, 2100)]
TM_PLANES = [
    plane("/host:CPU", [("python", [
        ev("bench.window.open", T, T), ev("bench.window.close", T + 2000,
                                          T + 2000)]
        + [ev("bench.tick", T + a, T + b) for a, b in TICKS])]),
    plane("/device:TPU:0", [("XLA Ops", [
        ev("%while.19", T + 60, T + 390), ev("%while.20", T + 70, T + 200),
        ev("%while.21", T + 200, T + 380),
        ev("%while.24", T + 410, T + 880), ev("%fusion.8", T + 500, T + 600),
        ev("%and_reduce_fusion.5", T + 905, T + 960),
        ev("%fusion.9", T + 1160, T + 1400),
        ev("%fusion.10", T + 1510, T + 1520),
        ev("%while.24", T + 1530, T + 1800),
        ev("%while.19", T + 1960, T + 1990)])]),
]
TM_SPANS = [
    span("tm.flush", _host(50), _host(395), flush_rows=64,
         flush_slots=128),
    span("tm.drain", _host(395), _host(900)),
    span("tm.analysis", _host(900), _host(980)),
    span("tm.policy", _host(980), _host(990)),
    span("tm.flush", _host(1150), _host(1500), flush_rows=64,
         flush_slots=128),
    span("tm.drain", _host(1500), _host(1850)),
    span("tm.flush", _host(1955), _host(1995), flush_rows=64,
         flush_slots=128),
]
HOST_TICKS = {"bench.tick": [(_host(a), _host(b)) for a, b in TICKS]}


def test_scope_device_time_is_a_union_inside_whole_ticks(recorded):
    recorded.extend(TM_SPANS)
    red = Reduction.from_planes(TM_PLANES)
    ctx = _ctx(red, HOST_TICKS)
    # tick 1: while.19 holds while.20 and while.21, so 330 and not
    # 330 + 130 + 180; tick 2: fusion.9's 240. The cut tick is left out
    assert _reader("flush_device_ms.catchup")(ctx) == pytest.approx(
        (330 + 240) / 2 * 1e-6)
    # tick 1: while.24 holds fusion.8 (470); tick 2: 10 + 270
    assert _reader("drain_device_ms.catchup")(ctx) == pytest.approx(
        (470 + 280) / 2 * 1e-6)
    # neither scope exceeds busy time in whole ticks, nor do both together
    busy_in_ticks = (330 + 470 + 55 + 240 + 10 + 270) / 2 * 1e-6
    assert (program.device_ms_per_tick(ctx, "tm.flush")
            + program.device_ms_per_tick(ctx, "tm.drain")) <= busy_in_ticks


def test_scope_device_time_needs_clocks_that_agree(recorded):
    recorded.extend(TM_SPANS)
    red = Reduction.from_planes(TM_PLANES)
    # the host saw the second tick 2 ms later than the trace did
    host = {"bench.tick": [(0.0, 1e-6), (0.002, 0.0020008),
                           (1.95e-6, 2.1e-6)]}
    ctx = _ctx(red, host)
    assert program.clock(ctx) is None
    assert _reader("flush_device_ms.catchup")(ctx) is None
    assert program.clock(_ctx(red, HOST_TICKS))(_host(50)) == T + 50


def test_program_span_and_counter_readers(recorded):
    recorded.extend(TM_SPANS)
    recorded.append(span("tm.analysis", 10.0, 10.05))
    ctx = _ctx(None, HOST_TICKS, t_close=20.0)
    assert _reader("analysis_ms.catchup")(ctx) == pytest.approx(
        (80e-9 + 0.05) / 2 * 1e3)
    assert _reader("flush_slot_use.catchup")(ctx) == 50.0
    # spans outside the window are not read
    late = _ctx(None, HOST_TICKS, t_open=5.0, t_close=20.0)
    assert _reader("flush_slot_use.catchup")(late) is None
    assert _reader("analysis_ms.catchup")(late) == pytest.approx(50.0)
    # a CPU-like trace, no device plane: no scope device time
    assert _reader("flush_device_ms.catchup")(
        _ctx(Reduction.from_planes(TM_PLANES[:1]), HOST_TICKS)) is None


def test_window_compiles_counts_the_window_traces_only(monkeypatch):
    times = [(1.0, "_enqueue_rows"), (2.0, "_enqueue_rows"), (2.5, "add"),
             (9.0, "add")]
    monkeypatch.setattr(obs_mod._JIT, "times", collections.deque(times))
    read = _reader("window_compiles.catchup")
    assert read(_ctx(None, {}, t_open=1.5, t_close=3.0)) == 2
    assert read(_ctx(None, {}, t_open=3.0, t_close=8.0)) == 0


def test_readers_find_nothing_without_the_program(recorded, monkeypatch):
    red = Reduction.from_planes(PLANES)
    ctx = _ctx(red, {"bench.tick": [(0.002, 0.5)]})
    # a program that records no span: the span readers find nothing
    for name in NEW[:4]:
        assert _reader(name)(ctx) is None, name
    # a program without repro.serve.obs: every reader finds nothing
    monkeypatch.setattr(program, "obs", lambda: None)
    for name in NEW:
        assert _reader(name)(ctx) is None, name


def test_existing_readers_read_what_they_read_before():
    red = Reduction.from_planes(PLANES)
    ctx = _ctx(red, {"bench.submit": [(0.0, 0.002), (0.01, 0.011)],
                     "bench.tick": [(0.002, 0.5), (0.6, 1.2)]})
    assert _reader("ingress_us_per_row")(ctx) == 0.7499999999999998
    assert _reader("tick_ms.catchup")(ctx) == 548.9999999999999
    assert _reader("train_mfu")(ctx) == 1568.3829059829056
    assert _reader("feedback_roofline")(ctx) == 20911.772079772076
    assert _reader("device_idle_share.catchup")(ctx) == 36.5


def test_traced_run_reads_the_program_spans_and_counters(cpu_run):  # noqa: F811
    w, conf, traffic = named("iris-catchup")
    # under the real cell's name, so its per-layer metrics are read
    res = cpu_run(({**w, "name": "iris-k4096-catchup"}, conf, traffic),
                  seconds=1.0, trace=True)
    assert res["correct"]
    m = res["metrics"]
    # the top-up stages 16 rows into every tenant's 32-slot lane a tick
    assert m["flush_slot_use.catchup"]["value"] == 50.0
    assert m["window_compiles.catchup"]["value"] == 0
    assert m["analysis_ms.catchup"]["value"] > 0
    # a CPU trace has no device plane, so no scope has device time
    assert "flush_device_ms.catchup" not in m
    assert "drain_device_ms.catchup" not in m
