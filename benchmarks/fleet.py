"""OnlineFleet serving benchmark: fleet drain vs K serial session drains.

Measures the replica-parallel online serving path (repro.serve.fleet)
against draining K independent ``OnlineSession`` machines one at a time —
the exact per-machine serial path the fleet replaced — asserting bitwise-
identical TA banks every run. The drain runs with monitoring compiled out
(the serving configuration), warm, on pre-filled buffers; each trial
re-fills every buffer with the same rows so both paths consume identical
offer streams.

Machine-readable results go to ``BENCH_fleet.json`` (override with env
``REPRO_BENCH_FLEET_JSON``). The headline field is
``results[fleet_drain].speedup`` — the fused fleet drain must stay >= 2x
over the serial K-session drain at K = 8 (gated in CI).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import init_runtime, init_state
from repro.core.online import OnlineSession
from repro.data import buffer as buf_mod
from repro.data import iris
from repro.serve.fleet import OnlineFleet

CFG = common.CFG

RESULTS: list[dict] = []


def _filled_buffer(xs, ys, cap):
    """A ring buffer holding rows [0, cap) (head=0, size=cap)."""
    return buf_mod.RingBuffer(
        data_x=jnp.asarray(xs[:cap], dtype=bool),
        data_y=jnp.asarray(ys[:cap], dtype=jnp.int32),
        head=jnp.int32(0),
        size=jnp.int32(cap),
    )


def drain_bench(K: int = 8, cap: int = 64, chunk: int = 16,
                trials: int = 5, *, cfg=None, data=None, rt=None) -> dict:
    """Fleet drain vs K serial session drains; bitwise equality asserted.

    Defaults measure the iris machine; ``cfg``/``data=(xs, ys)``/``rt``
    parameterize the same protocol over other workloads (benchmarks/scale.py
    runs it at MNIST widths) so the baseline semantics live in ONE place.
    Overriding ``cfg`` requires ``rt`` — the default runtime's s/T are
    iris-calibrated and would silently miscalibrate another machine.
    """
    if cfg is not None and rt is None:
        raise ValueError("pass rt= when overriding cfg= (default s/T are "
                         "iris-calibrated)")
    cfg = CFG if cfg is None else cfg
    xs, ys = iris.load() if data is None else data
    rt = init_runtime(cfg, s=3.0, T=15) if rt is None else rt
    seeds = list(range(K))
    # per-replica offer streams: distinct row rotations of the dataset
    rows = [np.roll(np.arange(len(xs)), -7 * r)[:cap] for r in range(K)]
    bufs = [_filled_buffer(xs[rows[r]], ys[rows[r]], cap) for r in range(K)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *bufs)

    def make_sessions():
        out = []
        for r in range(K):
            s = OnlineSession(cfg, init_state(cfg), rt, buffer_capacity=cap,
                              chunk=chunk, seed=seeds[r])
            s.ss = s.ss._replace(buf=bufs[r])
            out.append(s)
        return out

    def make_fleet():
        f = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                        buffer_capacity=cap, chunk=chunk, seed=seeds)
        f.ss = f.ss._replace(buf=stacked)
        return f

    # warm both paths (compile), keep outputs for the bitwise check
    warm_sessions = make_sessions()
    for s in warm_sessions:
        assert s.learn_available(cap) == cap
    warm_fleet = make_fleet()
    assert list(warm_fleet.drain(cap)) == [cap] * K
    want = np.stack([np.asarray(s.ss.tm.ta_state) for s in warm_sessions])
    got = np.asarray(warm_fleet.ss.tm.ta_state)
    if not np.array_equal(want, got):
        raise AssertionError(
            "fleet drain diverges from the serial K-session drain"
        )

    # timed: interleave so background host load skews both paths equally
    t_fleet, t_serial = float("inf"), float("inf")
    for _ in range(trials):
        fleet = make_fleet()
        jax.block_until_ready(fleet.ss.buf.data_x)
        t0 = time.perf_counter()
        fleet.drain(cap)
        jax.block_until_ready(fleet.ss.tm.ta_state)
        t_fleet = min(t_fleet, time.perf_counter() - t0)

        sessions = make_sessions()
        jax.block_until_ready(sessions[-1].ss.buf.data_x)
        t0 = time.perf_counter()
        for s in sessions:
            s.learn_available(cap)
        jax.block_until_ready(sessions[-1].ss.tm.ta_state)
        t_serial = min(t_serial, time.perf_counter() - t0)

    return {
        "n_replicas": K,
        "points_per_replica": cap,
        "chunk": chunk,
        "wall_s_fleet": t_fleet,
        "wall_s_serial_sessions": t_serial,
        "speedup": t_serial / t_fleet,
        "points_per_s_fleet": K * cap / t_fleet,
        "bitwise_identical": True,
    }


def main():
    RESULTS.clear()
    for K in (2, 8):
        row = drain_bench(K=K)
        name = "fleet_drain" if K == 8 else f"fleet_drain_k{K}"
        print(
            f"{name},{row['wall_s_fleet'] * 1e6:.1f},"
            f"K={K};points={row['points_per_replica']};"
            f"serial_s={row['wall_s_serial_sessions']:.4f};"
            f"speedup={row['speedup']:.2f}x;bitwise_identical=1"
        )
        RESULTS.append({"name": name, **row})

    out_path = os.environ.get("REPRO_BENCH_FLEET_JSON", "BENCH_fleet.json")
    payload = {
        "benchmark": "fleet",
        "backend": CFG.backend,
        "jax_backend": jax.default_backend(),
        "results": RESULTS,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path}")
    return payload


if __name__ == "__main__":
    common.enable_compile_cache()
    main()
