"""Concurrent-producer SLO traffic benchmark (DESIGN.md §14).

Drives the three standard scenario schedules (steady, bursty+drift,
fault-injected — serve/traffic.py) through a live :class:`TMService`
with one producer thread per replica, and reports the numbers a managed
online-learning service is judged by: sustained offers/s and p50/p99
submit/serve latency under real producer/consumer lock contention.

Every threaded run is then replayed through a fresh identical service
from a single thread and the final TA banks / RNG keys / policy state
compared bit for bit (``consistent_with_replay`` — the whole-system
parity oracle). A run that diverges aborts the benchmark: throughput
numbers from a service that computes different answers under threading
are not results.

Machine-readable results go to ``BENCH_traffic.json`` (override with env
``REPRO_BENCH_TRAFFIC_JSON``; ``REPRO_BENCH_TRAFFIC_POINTS`` and
``REPRO_BENCH_TRAFFIC_PRODUCERS`` size the load). CI gates a floor on
the steady scenario's sustained offers/s and a ceiling on every
scenario's p99 serve latency.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import numpy as np

from benchmarks import common
from repro.core import init_state
from repro.data import iris
from repro.serve import ServiceConfig, TMService
from repro.serve.service import AdaptPolicy
from repro.serve.traffic import (
    SCENARIOS,
    Scenario,
    fingerprint,
    fingerprints_equal,
    make_scripts,
    replay_single_caller,
    run_threaded,
    slo_summary,
)

CFG = common.CFG

RESULTS: list[dict] = []


def _sized(sc: Scenario, points: int) -> Scenario:
    """``sc`` rescaled to ``points`` offers per producer (fault point and
    class-introduction/drift fractions keep their relative position)."""
    if points == sc.points:
        return sc
    fault_at = (None if sc.fault_at is None
                else max(1, int(sc.fault_at * points / sc.points)))
    return dataclasses.replace(sc, points=points, fault_at=fault_at)


def _make_service(K: int, seed: int = 0) -> TMService:
    xs, ys = iris.load()
    return TMService(CFG, init_state(CFG), ServiceConfig(
        replicas=K, buffer_capacity=512, chunk=32, ingress_block=32,
        s=3.0, T=15, seed=seed,
        policy=AdaptPolicy(analyze_every=64),
    ), eval_x=np.asarray(xs), eval_y=np.asarray(ys))


def traffic_bench(scenario: Scenario, K: int = 4, *, seed: int = 0,
                  pace: float = 1.0) -> dict:
    """One scenario: threaded run -> SLO summary + bitwise replay check."""
    xs, ys = iris.load()
    scripts = make_scripts(scenario, np.asarray(xs), np.asarray(ys),
                           CFG.max_classes, K, seed=seed)
    live = _make_service(K, seed=seed)
    t0 = time.perf_counter()
    result = run_threaded(live, scripts, scenario=scenario, pace=pace)
    total_s = time.perf_counter() - t0

    twin = _make_service(K, seed=seed)
    replay_single_caller(twin, scripts, result, scenario=scenario)
    consistent = fingerprints_equal(fingerprint(live), fingerprint(twin))
    if not consistent:
        raise AssertionError(
            f"scenario {scenario.name!r}: threaded run diverged from its "
            "single-caller replay — threading changed WHAT was computed"
        )
    if not result.conserved():
        raise AssertionError(
            f"scenario {scenario.name!r}: offers not conserved "
            "(accepted + dropped != offers, or accepted != trained)"
        )
    row = slo_summary(result)
    row["total_s"] = total_s
    row["consistent_with_replay"] = consistent
    return row


def main():
    RESULTS.clear()
    points = int(os.environ.get("REPRO_BENCH_TRAFFIC_POINTS", "256"))
    K = int(os.environ.get("REPRO_BENCH_TRAFFIC_PRODUCERS", "4"))

    # Warm every jitted path (enqueue, drain, serve, analyze) at the
    # benchmark's shapes so no scenario's timing pays compilation.
    warm = _sized(dataclasses.replace(SCENARIOS["fault_injected"],
                                      fault_at=8), 16)
    traffic_bench(warm, K=K, pace=0.0)

    for name, sc in SCENARIOS.items():
        row = traffic_bench(_sized(sc, points), K=K)
        print(
            f"traffic_{name},{row['wall_s'] * 1e6:.1f},"
            f"producers={K};offers={row['offers']};"
            f"offers_per_s={row['offers_per_s']:.0f};"
            f"serve_p50_us={row['serve_p50_s'] * 1e6:.0f};"
            f"serve_p99_us={row['serve_p99_s'] * 1e6:.0f};"
            f"dropped={row['dropped']};rollbacks={row['rollbacks']};"
            f"consistent_with_replay=1"
        )
        RESULTS.append({"name": f"traffic_{name}", **row})

    out_path = os.environ.get("REPRO_BENCH_TRAFFIC_JSON",
                              "BENCH_traffic.json")
    payload = {
        "benchmark": "traffic",
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
