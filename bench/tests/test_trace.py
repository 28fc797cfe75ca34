"""The trace reduction on a small recorded trace: two chips, host spans."""
from types import SimpleNamespace as NS

import pytest

from bench.trace import Reduction, merged, union_seconds


def ev(name, a, b, stats=()):
    return NS(name=name, start_ns=a, end_ns=b, duration_ns=b - a,
              stats=list(stats))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines])


# window 100..1100 ns; chip 0 busy 150..400 (two overlapping ops) and
# 700..800; chip 1 busy 100..1100 minus 500..600; the host ran a tick
# over 100..650 and a submit over 650..1000
PLANES = [
    plane("/host:CPU", [("python", [
        ev("bench.window.open", 100, 100), ev("bench.tick", 100, 650),
        ev("bench.window.close", 1100, 1100),
        ev("bench.submit", 650, 1000), ev("other", 0, 50)])]),
    plane("/device:TPU:0", [
        ("XLA Ops", [ev("fusion.1", 150, 300),
                     ev("feedback_plane_replicated", 250, 400,
                        [("long_name", "custom-call")]),
                     ev("fusion.1", 700, 800), ev("early", 0, 120)]),
        ("XLA Modules", [ev("jit_step", 0, 5000)])]),
    plane("/device:TPU:1", [
        ("XLA Ops", [ev("fusion.2", 100, 500), ev("fusion.2", 600, 1100)])]),
]


def test_union_and_merge():
    assert union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert merged([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]


def test_busy_is_union_averaged_over_chips():
    red = Reduction.from_planes(PLANES)
    assert red.chips == 2
    assert red.window_s == pytest.approx(1000e-9)
    # chip 0: 100..120 (clipped) + 150..400 + 700..800 = 370; chip 1: 900
    assert red.busy_s == pytest.approx((370 + 900) / 2 * 1e-9)


def test_kernel_time_by_pattern():
    red = Reduction.from_planes(PLANES)
    assert red.op_seconds(r"feedback_plane") == pytest.approx(150e-9)
    assert red.op_seconds(r"custom-call") == pytest.approx(150e-9)
    top = red.top_ops(2)
    assert top[0][0] == "fusion.2"
    assert top[0][1] == pytest.approx(900e-9 / 2)


def test_idle_gaps_named_by_host_span():
    red = Reduction.from_planes(PLANES)
    gaps = dict(red.idle_gaps())
    # chip 0 idle: 120..150 and 400..700 (mostly inside the tick),
    # 800..1100 (mostly inside the submit)
    assert gaps == {"bench.tick": pytest.approx(330e-9),
                    "bench.submit": pytest.approx(300e-9)}


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        Reduction.from_planes(PLANES[1:])


def test_window_ends_where_a_full_line_stops(monkeypatch):
    from bench import trace

    monkeypatch.setattr(trace, "EVENT_LIMIT", 4096 + 5)
    # no line holds 5 events: the window stands
    assert Reduction.from_planes(PLANES).window_s == pytest.approx(1000e-9)
    monkeypatch.setattr(trace, "EVENT_LIMIT", 4096 + 4)
    red = Reduction.from_planes(PLANES)
    # chip 0's line is full at 4 events, the last recorded ending at 800
    assert red.window_s == pytest.approx(700e-9)
    assert red.busy_s == pytest.approx((370 + 600) / 2 * 1e-9)
