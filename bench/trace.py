"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read.

The window is the host span ``bench.window``; device planes are
``/device:TPU:<n>`` and their operations are the events of the line named
``XLA Ops``. Busy time is the union of those operations' intervals inside
the window, per chip, averaged over the chips. An idle gap is a stretch of
the window with no operation on the chip; each is named by the ``bench.*``
host span that overlaps it most (``host:none`` where no call was in flight).

    python bench/trace.py <dir or .xplane.pb>   # dump planes, lines, top ops
"""
from __future__ import annotations

import glob
import os
import re
import sys

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the profiler keeps at most this many events of a line (a v5e trace of the
# iris ingress loop stopped at 6,291,419); past it a device's later
# operations are missing, so the window then ends at the last one recorded
EVENT_LIMIT = 6 * 2**20


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _stats_text(ev) -> str:
    try:
        return " ".join(str(v) for _, v in ev.stats)
    except Exception:
        return ""


def short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = s8[...]
    fusion(...)`` becomes ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union_seconds(intervals) -> float:
    """Length of the union of [a, b) intervals, in the intervals' unit."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def merged(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class Reduction:
    """The window's device operations and host spans, in trace nanoseconds.

    ``host`` maps a span name to its (start, end) list; ``ops`` holds, per
    chip, (name, start, end, detail) of every operation clipped to the
    window."""

    def __init__(self, window: tuple, host: dict, ops: list):
        self.t0, self.t1 = window
        self.host = host
        self.ops = ops

    @classmethod
    def from_planes(cls, planes) -> "Reduction":
        host: dict = {}
        devices = []
        for pl in planes:
            if DEVICE_PLANE.match(pl.name):
                devices.append(pl)
            elif pl.name.startswith("/host:"):
                for ln in pl.lines:
                    for ev in ln.events:
                        if ev.name.startswith("bench."):
                            host.setdefault(ev.name, []).append(
                                (ev.start_ns, ev.end_ns))
        opened = host.get("bench.window.open")
        closed = host.get("bench.window.close")
        if not opened or not closed:
            raise ValueError("the trace holds no bench.window marks")
        t0, t1 = opened[0][0], closed[0][1]
        lines = [[ln for ln in pl.lines if ln.name == OPS_LINE]
                 for pl in sorted(devices, key=lambda p: p.name)]
        for ln in (ln for lns in lines for ln in lns):
            evs = list(ln.events)
            if len(evs) >= EVENT_LIMIT - 4096:
                t1 = min(t1, max(ev.end_ns for ev in evs))
        ops = []
        for lns in lines:
            evs = []
            for ln in lns:
                for ev in ln.events:
                    a, b = max(ev.start_ns, t0), min(ev.end_ns, t1)
                    if b > a:
                        evs.append((ev.name, a, b, _stats_text(ev)))
            ops.append(evs)
        return cls((t0, t1), host, ops)

    @classmethod
    def from_file(cls, path: str) -> "Reduction":
        from jax.profiler import ProfileData

        return cls.from_planes(ProfileData.from_file(find_xplane(path)).planes)

    @property
    def chips(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds with an operation on the device, averaged over chips."""
        if not self.ops:
            return 0.0
        return sum(union_seconds((a, b) for _, a, b, _ in evs)
                   for evs in self.ops) * 1e-9 / len(self.ops)

    def op_seconds(self, pattern: str) -> float:
        """Summed device seconds of the operations whose name or details
        match ``pattern``, over all chips."""
        rx = re.compile(pattern)
        return sum(b - a for evs in self.ops for n, a, b, d in evs
                   if rx.search(n) or rx.search(d)) * 1e-9

    def top_ops(self, n: int = 10) -> list:
        """[name, seconds] of the operations that took most device time,
        averaged over chips."""
        acc: dict = {}
        for evs in self.ops:
            for name, a, b, _ in evs:
                key = short(name)
                acc[key] = acc.get(key, 0.0) + (b - a) * 1e-9
        k = max(1, len(self.ops))
        return [[name, s / k] for name, s in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[what the host was doing, idle seconds] over all gaps of the
        first chip, summed by name, largest first."""
        if not self.ops:
            return []
        busy = merged((a, b) for _, a, b, _ in self.ops[0])
        gaps, prev = [], self.t0
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if self.t1 > prev:
            gaps.append((prev, self.t1))
        spans = [(name, a, b) for name, ivs in self.host.items()
                 if not name.startswith("bench.window") for a, b in ivs]
        acc: dict = {}
        for ga, gb in gaps:
            best, label = 0, "host:none"
            for name, a, b in spans:
                ov = min(b, gb) - max(a, ga)
                if ov > best:
                    best, label = ov, name
            acc[label] = acc.get(label, 0.0) + (gb - ga) * 1e-9
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def dump(path: str) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    for pl in pd.planes:
        lines = list(pl.lines)
        print(f"plane {pl.name!r}: {len(lines)} lines")
        for ln in lines:
            evs = list(ln.events)
            if not evs:
                continue
            print(f"  line {ln.name!r}: {len(evs)} events, "
                  f"{min(e.start_ns for e in evs)}..{max(e.end_ns for e in evs)}")
            if DEVICE_PLANE.match(pl.name) or ln.name.startswith("python"):
                acc: dict = {}
                for e in evs:
                    k = e.name
                    s = acc.setdefault(k, [0, 0.0, _stats_text(e)[:300]])
                    s[0] += 1
                    s[1] += e.duration_ns * 1e-9
                for k, (c, s, d) in sorted(acc.items(),
                                           key=lambda kv: -kv[1][1])[:25]:
                    print(f"    {s:.6f}s x{c} {k!r} | {d}")


if __name__ == "__main__":
    dump(sys.argv[1])
