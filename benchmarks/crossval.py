"""Replica-parallel cross-validation engine benchmark (paper goal ii, §5).

Measures the fused sweep program (repro.eval.crossval.CrossValRun) against
the pre-engine vmap-of-scan paths it replaced, asserting bit-identical
results every run:

* ``crossval_sweep``  — the (s x T x orderings) hyperparameter sweep:
  engine vs the legacy ``hpsearch.grid_search_device`` nested-vmap program.
* ``crossval_system`` — the Fig-3 system flow over all orderings:
  engine vs ``vmap(manager.run_system)`` (the old ``run_orderings`` body).

Every row is written machine-readable to ``BENCH_crossval.json`` (override
with env ``REPRO_BENCH_CROSSVAL_JSON``) so the sweep speedup is tracked
across PRs next to BENCH_throughput.json. The headline field is
``results[crossval_sweep].speedup`` — the replica-parallel engine must stay
>= 2x over the vmap-of-scan baseline on CPU.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import hpsearch
from repro.core import manager as mgr
from repro.core import init_runtime, init_state
from repro.data import blocks
from repro.eval.crossval import CrossValRun

CFG = common.CFG

RESULTS: list[dict] = []

S_GRID = (1.375, 2.0, 3.0)
T_GRID = (5, 10, 15)
N_EPOCHS = 10


def _min_time(fn, *, trials=3, inner=1):
    """Min seconds/call over interleave-friendly trials (first call warms)."""
    out = jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) / inner)
    return best, out


def _emit(name: str, us_per_call: float, derived: str, **extra):
    print(f"{name},{us_per_call:.1f},{derived}")
    RESULTS.append({"name": name, "us_per_call": us_per_call, **extra})


def sweep_bench(n_orderings: int, seed: int = 0, *, cfg=None, osets=None,
                s_values=S_GRID, T_values=T_GRID,
                n_epochs=N_EPOCHS) -> dict:
    """Engine vs legacy nested-vmap sweep; bitwise equality asserted.

    Defaults measure the iris machine; ``cfg``/``osets`` parameterize the
    same protocol over other workloads (benchmarks/scale.py runs it at
    MNIST widths) so the legacy-baseline semantics live in ONE place.
    """
    cfg = CFG if cfg is None else cfg
    if osets is None:
        osets, _ = blocks.iris_paper_sets(n_orderings=n_orderings)
    off = (jnp.asarray(osets.offline_x), jnp.asarray(osets.offline_y))
    val = (jnp.asarray(osets.validation_x), jnp.asarray(osets.validation_y))
    keys = jax.random.split(jax.random.PRNGKey(seed), n_orderings)
    s_grid = jnp.asarray(s_values, jnp.float32)
    T_grid = jnp.asarray(T_values, jnp.int32)

    def legacy():
        return hpsearch.grid_search_device(
            cfg, s_grid, T_grid, off, val, keys, n_epochs
        )

    run = CrossValRun(cfg)

    def engine():
        return run.sweep(
            *off, *val, s_values, T_values, n_epochs=n_epochs, seed=seed
        ).val_accuracy

    # Interleave so background host load skews both paths equally.
    t_eng, t_leg = float("inf"), float("inf")
    acc_eng = acc_leg = None
    for _ in range(3):
        t, acc_eng = _min_time(engine, trials=1)
        t_eng = min(t_eng, t)
        t, acc_leg = _min_time(legacy, trials=1)
        t_leg = min(t_leg, t)
    if not np.array_equal(np.asarray(acc_eng), np.asarray(acc_leg)):
        raise AssertionError(
            "replica-parallel sweep diverges from the vmap-of-scan baseline"
        )

    R = len(s_values) * len(T_values) * n_orderings
    return {
        "cells": R,
        "replicas": R,
        "wall_s_engine": t_eng,
        "wall_s_legacy_vmap": t_leg,
        "speedup": t_leg / t_eng,
        "replicas_per_s": R / t_eng,
        "bitwise_identical": True,
    }


def system_bench(n_orderings: int, n_cycles: int = 16, seed: int = 0) -> dict:
    """Engine vs vmap(run_system) over orderings; bitwise equality asserted."""
    sets, O = common.build_sets(n_orderings)
    sys_cfg = mgr.SystemConfig(n_offline_epochs=N_EPOCHS, n_online_cycles=n_cycles)
    schedule = mgr.make_schedule(online_s=1.0)
    rt = init_runtime(CFG, s=1.375, T=15)
    states = jax.vmap(lambda _: init_state(CFG))(jnp.arange(O))
    keys = jax.random.split(jax.random.PRNGKey(seed), O)

    legacy_fn = jax.vmap(
        lambda st, ss, k: mgr.run_system(CFG, sys_cfg, st, rt, ss, schedule, k)
    )
    def legacy():
        return legacy_fn(states, sets, keys)[1]

    run = CrossValRun(CFG)

    def engine():
        return run.system(sys_cfg, states, rt, sets, schedule, keys).accuracies

    t_eng, t_leg = float("inf"), float("inf")
    acc_eng = acc_leg = None
    for _ in range(3):
        t, acc_eng = _min_time(engine, trials=1)
        t_eng = min(t_eng, t)
        t, acc_leg = _min_time(legacy, trials=1)
        t_leg = min(t_leg, t)
    if not np.array_equal(np.asarray(acc_eng), np.asarray(acc_leg)):
        raise AssertionError(
            "replica-parallel system run diverges from vmap(run_system)"
        )

    return {
        "orderings": O,
        "n_cycles": n_cycles,
        "wall_s_engine": t_eng,
        "wall_s_legacy_vmap": t_leg,
        "speedup": t_leg / t_eng,
        "replicas_per_s": O / t_eng,
        "bitwise_identical": True,
    }


def analyze_fused_bench(n_orderings: int, grid: int = 9, seed: int = 0) -> dict:
    """Fused single-contraction 3-set analysis vs three analyze_replicated
    calls (the per-cycle analysis block of the system path); bitwise
    equality asserted."""
    from functools import partial

    from repro.core import accuracy as acc_mod
    from repro.eval.crossval import grid_layout, replicate_state

    sets, O = common.build_sets(n_orderings)
    R = grid * O
    s_rep, T_rep = grid_layout(
        jnp.linspace(1.375, 3.0, grid), (15,), O
    )
    rt = init_runtime(CFG)._replace(s=s_rep, T=T_rep)
    states = replicate_state(CFG, R)
    triple = [
        (sets.offline_x, sets.offline_y, sets.offline_valid),
        (sets.validation_x, sets.validation_y, sets.validation_valid),
        (sets.online_x, sets.online_y, sets.online_valid),
    ]

    @partial(jax.jit, static_argnums=0)
    def fused(cfg, st, r):
        return acc_mod.analyze_sets_replicated(cfg, st, r, triple)

    @partial(jax.jit, static_argnums=0)
    def separate(cfg, st, r):
        return jnp.stack(
            [acc_mod.analyze_replicated(cfg, st, r, x, y, v)
             for x, y, v in triple],
            axis=-1,
        )

    t_fused, out_fused = _min_time(lambda: fused(CFG, states, rt), trials=5)
    t_sep, out_sep = _min_time(lambda: separate(CFG, states, rt), trials=5)
    if not np.array_equal(np.asarray(out_fused), np.asarray(out_sep)):
        raise AssertionError(
            "fused 3-set analysis diverges from three separate calls"
        )
    return {
        "replicas": R,
        "orderings": O,
        "wall_s_fused": t_fused,
        "wall_s_separate": t_sep,
        "speedup": t_sep / t_fused,
        "bitwise_identical": True,
    }


def main(n_orderings: int = 24):
    RESULTS.clear()

    row = sweep_bench(n_orderings)
    _emit(
        "crossval_sweep", row["wall_s_engine"] * 1e6,
        f"cells={row['cells']};replicas_per_s={row['replicas_per_s']:.1f};"
        f"legacy_s={row['wall_s_legacy_vmap']:.3f};"
        f"speedup={row['speedup']:.2f}x;bitwise_identical=1",
        **row,
    )

    row = system_bench(n_orderings)
    _emit(
        "crossval_system", row["wall_s_engine"] * 1e6,
        f"orderings={row['orderings']};"
        f"replicas_per_s={row['replicas_per_s']:.1f};"
        f"legacy_s={row['wall_s_legacy_vmap']:.3f};"
        f"speedup={row['speedup']:.2f}x;bitwise_identical=1",
        **row,
    )

    row = analyze_fused_bench(n_orderings)
    _emit(
        "crossval_analyze_fused", row["wall_s_fused"] * 1e6,
        f"replicas={row['replicas']};"
        f"separate_s={row['wall_s_separate']:.4f};"
        f"speedup={row['speedup']:.2f}x;bitwise_identical=1",
        **row,
    )

    out_path = os.environ.get("REPRO_BENCH_CROSSVAL_JSON", "BENCH_crossval.json")
    payload = {
        "benchmark": "crossval",
        "backend": CFG.backend,
        "jax_backend": jax.default_backend(),
        "grid": {"s": list(S_GRID), "T": list(T_GRID), "n_epochs": N_EPOCHS},
        "results": RESULTS,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path}")
    return payload


if __name__ == "__main__":
    common.enable_compile_cache()
    main(int(os.environ.get("REPRO_BENCH_ORDERINGS", "24")))
