"""Thousand-replica residency benchmark: K fleets on bounded device slots.

Drives a ``TMService`` with ``resident`` device slots (DESIGN.md §15)
under sparse personalization traffic — each round a random subset of
replicas receives datapoints and the fleet ticks — at K in {64, 1024,
4096}, the ROADMAP's thousand-replica scale, on whatever device mesh is
present (the CI job forces a 4-host-device topology). The evicted/
reactivated fleet is asserted BITWISE equal to an always-resident
unsharded twin driven with budgets masked by ``buffered > 0`` (the
residency drain's sweep criterion — see tests/test_residency.py), so the
numbers measure a correct fleet, not a drifting one.

Measured per K: adapt throughput (drained points/s through the
submit/tick loop, activation thrash included), offered rows/s, the
explicit activate/evict cohort latency (host snapshot <-> device slot
moves, per replica), and — the §17 tentpole number — the batched
datapath's speedup over PR 8's synchronous per-cohort baseline
(``batched_moves=False``, same traffic, same seeds):
``speedup_vs_percohort``. An extra ``residency_auto`` row drives
``resident="auto"`` through a dense->sparse traffic shift and records
the re-partition trajectory.

Machine-readable results go to ``BENCH_residency.json`` (override with
env ``REPRO_BENCH_RESIDENCY_JSON``). CI gates (benchmarks/gates.py)
every row's ``bitwise_identical``, ``residency_k1024.trained_per_s`` on
the 4-device mesh, and ``residency_k4096.speedup_vs_percohort >= 1.5``.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks import common
from repro.core import init_state
from repro.serve import AdaptPolicy, ServiceConfig, TMService

CFG = common.CFG

RESULTS: list[dict] = []

# iris rows as the traffic source (the paper's machine: f = 16)
from repro.data import iris  # noqa: E402

_XS, _YS = (np.asarray(a) for a in iris.load())


def _mesh():
    n = len(jax.devices())
    return jax.make_mesh((n,), ("replicas",)) if n > 1 else None


def _make(K, resident, mesh, seed=0, batched=True):
    return TMService(CFG, init_state(CFG), ServiceConfig(
        replicas=K, buffer_capacity=16, chunk=8, ingress_block=8,
        s=3.0, T=15, seed=seed, resident=resident, mesh=mesh,
        batched_moves=batched,
        policy=AdaptPolicy(analyze_every=10 ** 9),  # drain-only loop
    ))


def _drive(svc, rounds, active, *, twin=None, rng_seed=0):
    """``rounds`` of sparse traffic: ``active`` random replicas get one
    row each, then the fleet ticks. Optionally co-drives an
    always-resident ``twin`` with buffered-masked budgets."""
    rng = np.random.default_rng(rng_seed)
    K = svc.n_replicas
    for r in range(rounds):
        ids = rng.choice(K, size=min(active, K), replace=False)
        mask = np.zeros(K, dtype=bool)
        mask[ids] = True
        i = int(rng.integers(0, len(_XS)))
        svc.submit_rows(_XS[i], int(_YS[i]), mask)
        if twin is not None:
            twin.submit_rows(_XS[i], int(_YS[i]), mask)
            svc.flush()
            buffered = svc.buffered > 0
            svc.tick()
            twin.tick(np.where(buffered, twin.chunk, 0))
        else:
            svc.tick()
    svc.flush()
    if twin is not None:
        twin.flush()


def _assert_twin_bitwise(svc, twin):
    a = jax.tree.leaves((svc.ss, svc.rng_keys, svc.steps))
    b = jax.tree.leaves((twin.ss, twin.rng_keys, twin.steps))
    for la, lb in zip(a, b):
        if not np.array_equal(np.asarray(la), np.asarray(lb)):
            raise AssertionError(
                "residency fleet diverged from always-resident twin"
            )


def _move_latency(svc, cycles=4):
    """Mean per-replica latency of explicit evict -> activate cohort
    moves (host LRU store <-> device slots), on resident-sized cohorts."""
    R = svc.n_resident
    cohort = np.nonzero(svc.resident)[0][:R]
    t_evict = t_act = 0.0
    for _ in range(cycles):
        t0 = time.perf_counter()
        svc.evict(cohort)
        t_evict += time.perf_counter() - t0
        t0 = time.perf_counter()
        svc.activate(cohort)
        jax.block_until_ready(svc._ss.tm.ta_state)
        t_act += time.perf_counter() - t0
    n = cycles * len(cohort)
    return t_evict / n, t_act / n


def residency_bench(K: int, resident: int, rounds: int, active: int,
                    *, mesh=None, twin_check: bool = True) -> dict:
    """One K-point: sparse-traffic adapt loop + move latency + twin."""
    # correctness pass (untimed): sharded residency fleet vs unsharded
    # always-resident twin, bitwise
    bitwise = None
    if twin_check:
        svc = _make(K, resident, mesh)
        twin = _make(K, None, None)
        _drive(svc, rounds, active, twin=twin)
        _assert_twin_bitwise(svc, twin)
        bitwise = True

    # timed pass (twin bookkeeping off the clock); fresh service so the
    # LRU starts cold exactly like the correctness pass
    svc = _make(K, resident, mesh)
    _drive(svc, 2, active)           # warm the compiled paths
    trained0 = int(svc.steps.sum())
    t0 = time.perf_counter()
    _drive(svc, rounds, active, rng_seed=1)
    wall = time.perf_counter() - t0
    trained = int(svc.steps.sum()) - trained0

    # per-cohort baseline (PR 8's synchronous gather/scatter path), same
    # traffic and seeds — the §17 speedup denominator
    base = _make(K, resident, mesh, batched=False)
    _drive(base, 2, active)
    base0 = int(base.steps.sum())
    t0 = time.perf_counter()
    _drive(base, rounds, active, rng_seed=1)
    wall_base = time.perf_counter() - t0
    trained_base = int(base.steps.sum()) - base0
    assert trained_base == trained, "baseline consumed different traffic"

    evict_s, act_s = _move_latency(svc)
    return {
        "n_replicas": K,
        "resident": resident,
        "rounds": rounds,
        "active_per_round": active,
        "devices": len(jax.devices()),
        "sharded": mesh is not None,
        "wall_s": wall,
        "trained_points": trained,
        "trained_per_s": trained / wall,
        "offers_per_s": rounds * active / wall,
        "percohort_wall_s": wall_base,
        "percohort_trained_per_s": trained_base / wall_base,
        "speedup_vs_percohort": wall_base / wall,
        "activations": svc.obs.counters()["residency.activations"],
        "evictions": svc.obs.counters()["residency.evictions"],
        "evict_latency_s_per_replica": evict_s,
        "activate_latency_s_per_replica": act_s,
        "bitwise_identical": bitwise,
    }


def auto_residency_bench(K: int, rounds: int, *, mesh=None) -> dict:
    """resident='auto' observability row: a dense->sparse traffic shift
    and the re-partition trajectory it provokes, with the twin bitwise
    assertion held across every re-partition."""
    svc = _make(K, "auto", mesh)
    twin = _make(K, None, None)
    r0 = svc.n_resident
    half = rounds // 2
    _drive(svc, half, K, twin=twin)                # dense: grow
    grown = svc.n_resident
    _drive(svc, rounds - half, 1, twin=twin,       # sparse: shrink
           rng_seed=1)
    _assert_twin_bitwise(svc, twin)
    return {
        "n_replicas": K,
        "rounds": rounds,
        "devices": len(jax.devices()),
        "sharded": mesh is not None,
        "resident_initial": r0,
        "resident_after_dense": grown,
        "resident_final": svc.n_resident,
        "repartitions": int(svc.repartitions),
        "ewma_active": float(svc._res.ewma_active),
        "bitwise_identical": True,
    }


def main():
    RESULTS.clear()
    mesh = _mesh()
    # resident divides the device count (grid-major sharding of the slot
    # plane); traffic stays sparse — the personalization regime where a
    # round touches a sliver of the fleet.
    for K, resident, rounds, active in (
        (64, 16, 30, 16),
        (1024, 64, 12, 32),
        (4096, 64, 6, 32),
    ):
        row = residency_bench(K, resident, rounds, active, mesh=mesh)
        name = f"residency_k{K}"
        print(
            f"{name},{row['wall_s'] * 1e6:.1f},"
            f"resident={resident};devices={row['devices']};"
            f"trained_per_s={row['trained_per_s']:.0f};"
            f"speedup_vs_percohort={row['speedup_vs_percohort']:.2f};"
            f"act_us={row['activate_latency_s_per_replica'] * 1e6:.0f};"
            f"evict_us={row['evict_latency_s_per_replica'] * 1e6:.0f};"
            f"bitwise_identical=1"
        )
        RESULTS.append({"name": name, **row})

    row = auto_residency_bench(64, 24, mesh=mesh)
    print(
        f"residency_auto,0.0,"
        f"resident={row['resident_initial']}->"
        f"{row['resident_after_dense']}->{row['resident_final']};"
        f"repartitions={row['repartitions']};bitwise_identical=1"
    )
    RESULTS.append({"name": "residency_auto", **row})

    out_path = os.environ.get("REPRO_BENCH_RESIDENCY_JSON",
                              "BENCH_residency.json")
    payload = {
        "benchmark": "residency",
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
