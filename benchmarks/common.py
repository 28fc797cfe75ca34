"""Shared harness for the paper-figure benchmarks.

Builds the paper's iris setup (16 clauses, T=15, s=1.375 offline / 1.0
online, 10 offline epochs, sets 30/60/60, offline limited to 20 rows) and
runs all cross-validation orderings as ONE vmapped program.

Every flow is dataset-parametric: ``dataset="mnist"`` swaps in the
booleanized MNIST-scale digit workload (f = side**2 boolean inputs, 10
classes, same 150-row/5-block CV geometry) with the ``tm_mnist`` preset —
no host-side reshaping anywhere downstream, the datapath width just
changes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.tm_iris import CONFIG as TM_SYS
from repro.configs.tm_iris import TMSystemParams
from repro.core import init_runtime, init_state
from repro.core import manager as mgr
from repro.data import blocks
from repro.launch import compile_cache

CFG = TM_SYS.tm


def enable_compile_cache() -> str:
    """JAX's persistent compile cache for a benchmark run: the checkout's
    fixed ``.jax_cache/`` unless ``JAX_COMPILATION_CACHE_DIR`` is set."""
    return compile_cache.enable(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _dataset(dataset: str):
    """ONE dispatch point per dataset: (params_fn(side), sets_fn(n, side))."""
    if dataset == "iris":
        return (lambda side: TM_SYS,
                lambda n, side: blocks.iris_paper_sets(n_orderings=n))
    if dataset == "mnist":
        from repro.configs import tm_mnist

        return (lambda side: tm_mnist.config_for_side(
                    tm_mnist.SIDE if side is None else side),
                lambda n, side: blocks.mnist_paper_sets(
                    n_orderings=n, side=side))
    raise ValueError(f"unknown dataset {dataset!r} (iris | mnist)")


def system_params(dataset: str = "iris", side: int | None = None) -> TMSystemParams:
    """The per-dataset system preset (iris default; mnist at ``side``)."""
    return _dataset(dataset)[0](side)


def build_sets(n_orderings: int, offline_limit: int | None = 20,
               dataset: str = "iris", side: int | None = None):
    """Stacked per-ordering Sets + keys (leading axis = ordering)."""
    osets, _spec = _dataset(dataset)[1](n_orderings, side)
    O, n_off = osets.offline_y.shape
    train_valid = np.ones((O, n_off), dtype=bool)
    if offline_limit is not None:
        train_valid[:, offline_limit:] = False  # §5.1: train on 20 of 30
    sets = mgr.Sets(
        offline_x=jnp.asarray(osets.offline_x),
        offline_y=jnp.asarray(osets.offline_y),
        offline_valid=jnp.ones((O, n_off), dtype=bool),  # analyze all 30
        validation_x=jnp.asarray(osets.validation_x),
        validation_y=jnp.asarray(osets.validation_y),
        validation_valid=jnp.ones(osets.validation_y.shape, dtype=bool),
        online_x=jnp.asarray(osets.online_x),
        online_y=jnp.asarray(osets.online_y),
        online_valid=jnp.ones(osets.online_y.shape, dtype=bool),
        offline_train_valid=jnp.asarray(train_valid),
    )
    return sets, O


def run_schedule(schedule, *, n_orderings=24, n_cycles=16,
                 offline_limit: int | None = 20, seed=0,
                 dataset: str = "iris", side: int | None = None):
    """Mean accuracy curves [1+n_cycles, 3] over orderings + wall time.

    Thin caller of the replica-parallel engine: every ordering's Fig-3 run
    advances in one fused plane per datapoint (repro.eval.crossval).
    """
    from repro.eval.crossval import CrossValRun

    params = system_params(dataset, side)
    cfg = params.tm
    sets, O = build_sets(n_orderings, offline_limit, dataset, side)
    sys_cfg = mgr.SystemConfig(
        n_offline_epochs=params.n_offline_epochs, n_online_cycles=n_cycles
    )
    rt = init_runtime(cfg, s=params.s_offline, T=params.T)
    states = jax.vmap(lambda _: init_state(cfg))(jnp.arange(O))
    keys = jax.random.split(jax.random.PRNGKey(seed), O)

    res = CrossValRun(cfg).system(sys_cfg, states, rt, sets, schedule, keys)
    accs = np.asarray(res.accuracies)    # [O, 1+n_cycles, 3]
    activity = np.asarray(res.activity)  # [O, n_cycles]
    return accs.mean(axis=0), activity.mean(axis=0), res.wall_s, O


def curve_csv(name: str, curve: np.ndarray) -> str:
    """accuracy curve -> csv rows `name,cycle,offline,validation,online`."""
    rows = []
    for i, (a, b, c) in enumerate(curve):
        rows.append(f"{name},{i},{a:.4f},{b:.4f},{c:.4f}")
    return "\n".join(rows)
