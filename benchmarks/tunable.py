"""Runtime-tunable serving benchmark: accuracy vs compute at fixed budgets.

Measures the DESIGN.md §16 budgeted serve path on a trained machine:
clauses are ranked by calibration vote contribution on the TRAIN split,
then the held-out split is served at budget in {100%, 50%, 25%, 12.5%}
through the compacted pruned kernels (4-bit calibration weights folded
into the vote). Per budget point: held-out accuracy, seconds/batch, and
speedup over the full (non-pruned) serve path — the accuracy-vs-speedup
curve a latency-pressured deployment trades along.

Workloads: the paper's iris machine (f = 16, 16 clauses) and the
MNIST-scale digit workload at f in {196, 784} with the over-provisioned
clause budget (128 clauses, §3.1.1 headroom — the regime where pruning
has redundancy to spend). Rankings are polarity-balanced (best positive
and negative clauses interleave — a plain score sort de-calibrates the
+-vote and costs 4-7 points at budget 25%). Both backends run; trials
interleave full/pruned calls and keep per-path minima so host noise
skews no path.

In-script asserts (the CI ``tunable`` job re-checks from the JSON):
budget=100% with unit weights is BITWISE the plain serve path, and on
pallas at f=784 the 25% budget serves >= 2x faster than full budget with
a held-out accuracy drop of at most 2 points.

Machine-readable results go to ``BENCH_tunable.json`` (override with env
``REPRO_BENCH_TUNABLE_JSON``).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import feedback as fb
from repro.core import accuracy as acc_mod
from repro.core import tm as tm_mod
from repro.serve import tunable as tun

RESULTS: list[dict] = []

BUDGETS = (1.0, 0.5, 0.25, 0.125)
# unit weights: on these workloads the linear calibration weights buy
# nothing over balanced pruning and cost 2-3 points at full budget
# (measured — see DESIGN.md §16); the capability is exercised by the
# test suite, the measured curve serves unweighted.
WEIGHT_BITS = 0


def _time_once(fn, *args):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def _workload(name: str):
    """name -> (cfg, s, T, epochs, train_xy, test_xy)."""
    if name == "iris":
        from repro.configs.tm_iris import CONFIG as SYS
        from repro.data import iris

        xs, ys = iris.load()
        return (SYS.tm, SYS.s_offline, SYS.T, SYS.n_offline_epochs,
                (xs[:100], ys[:100]), (xs[100:], ys[100:]))
    side = int(name.split("-f", 1)[1]) if "-f" in name else None
    side = {196: 14, 784: 28}[side]
    from repro.configs import tm_mnist
    from repro.data import mnist

    sysp = tm_mnist.config_for_side(side)
    # over-provisioned clause budget (§3.1.1): headroom in reserve is
    # exactly what a runtime budget spends
    cfg = dataclasses.replace(sysp.tm, max_clauses=128)
    tr_x, tr_y, te_x, te_y = mnist.splits(n_train=200, n_test=250,
                                          side=side)
    return (cfg, sysp.s_offline, sysp.T, sysp.n_offline_epochs,
            (tr_x, tr_y), (te_x, te_y))


def _train(cfg, s, T, epochs, xs, ys, seed=0):
    rt = tm_mod.init_runtime(cfg, s=s, T=T)
    st = tm_mod.init_state(cfg, jax.random.PRNGKey(seed))
    xs_j, ys_j = jnp.asarray(xs), jnp.asarray(ys)
    epoch = jax.jit(
        lambda st, k: fb.train_datapoints(cfg, st, rt, xs_j, ys_j, k))
    key = jax.random.PRNGKey(seed + 1)
    for e in range(epochs):
        key, k = jax.random.split(key)
        st, _ = epoch(st, k)
    return jax.block_until_ready(st), rt


def tunable_bench(workload: str, backend: str, trained, *, rounds: int = 4,
                  reps: int = 3) -> list[dict]:
    """One (workload, backend) sweep over BUDGETS. Returns result rows.

    ``trained`` is the (state, rt, splits) from :func:`_train` — training
    is backend-bitwise-identical (the parity suite pins it), so both
    backends serve the SAME banks and the curves are comparable.
    """
    st, rt, (tr_x, tr_y), (te_x, te_y), cfg0 = trained
    cfg = dataclasses.replace(cfg0, backend=backend)
    te_xj, te_yj = jnp.asarray(te_x), jnp.asarray(te_y)
    J = cfg.max_clauses

    # calibrate on the TRAIN split (the held-out set stays held out);
    # polarity-balanced ranking, unit weights (see module docstring)
    score = np.asarray(tun.clause_scores(
        cfg, st, rt, jnp.asarray(tr_x), jnp.asarray(tr_y)))
    order = tun.rank_from_scores(
        score, np.asarray(tm_mod.clause_polarity(cfg)))
    weights = tun.weights_from_scores(score, WEIGHT_BITS)
    w_j = None if weights is None else jnp.asarray(weights)

    full = jax.jit(lambda st, x: tm_mod.predict_batch_(cfg, st, rt, x))
    acc_full = float(acc_mod.analyze(cfg, st, rt, te_xj, te_yj))

    # parity: budget=100% + unit weights == the plain path, bitwise
    sel_full = jnp.asarray(order)
    p_plain = np.asarray(full(st, te_xj))
    p_pruned = np.asarray(tm_mod.predict_batch_pruned(
        cfg, st, rt, te_xj, sel_full, None))
    if not np.array_equal(p_plain, p_pruned):
        raise AssertionError(
            f"{workload}/{backend}: full-budget pruned serve is not "
            "bitwise the plain serve path")

    pruned_fns = {}
    for b in BUDGETS:
        m = tun.m_for_budget(b, J)
        sel = jnp.asarray(order[:, :m])
        pruned_fns[b] = (
            jax.jit(lambda st, x, _sel=sel:
                    tm_mod.predict_batch_pruned_(cfg, st, rt, x, _sel,
                                                 w_j)),
            m,
        )

    # warm every path, then interleave trials: min per path
    _time_once(full, st, te_xj)
    for fn, _ in pruned_fns.values():
        _time_once(fn, st, te_xj)
    t_full = float("inf")
    t_budget = {b: float("inf") for b in BUDGETS}
    for _ in range(rounds):
        dt = min(_time_once(full, st, te_xj) for _ in range(reps))
        t_full = min(t_full, dt)
        for b, (fn, _) in pruned_fns.items():
            dt = min(_time_once(fn, st, te_xj) for _ in range(reps))
            t_budget[b] = min(t_budget[b], dt)

    rows = []
    for b in BUDGETS:
        fn, m = pruned_fns[b]
        acc = float(acc_mod.analyze_pruned(
            cfg, st, rt, te_xj, te_yj, jnp.asarray(order[:, :m]), w_j))
        speedup = t_full / t_budget[b]
        name = (f"tunable_{workload}_{backend}_b"
                f"{str(b).replace('.', 'p')}")
        print(f"{name},{t_budget[b] * 1e6:.1f},"
              f"m={m};acc={acc:.4f};acc_full={acc_full:.4f};"
              f"speedup={speedup:.2f}x;weight_bits={WEIGHT_BITS}")
        rows.append({
            "name": name,
            "workload": workload,
            "backend": backend,
            "budget": b,
            "m": m,
            "n_clauses": J,
            "n_features": cfg.n_features,
            "weight_bits": WEIGHT_BITS,
            "us_per_call": t_budget[b] * 1e6,
            "us_per_call_full": t_full * 1e6,
            "speedup_vs_full": speedup,
            "accuracy": acc,
            "accuracy_full": acc_full,
            "accuracy_drop": acc_full - acc,
            "bitwise_at_full_budget": True,
        })
    return rows


def main():
    RESULTS.clear()
    for workload in ("iris", "mnist-f196", "mnist-f784"):
        cfg, s, T, epochs, (tr_x, tr_y), (te_x, te_y) = _workload(workload)
        # train once on ref — training is backend-bitwise-identical
        st, rt = _train(dataclasses.replace(cfg, backend="ref"),
                        s, T, epochs, tr_x, tr_y)
        trained = (st, rt, (tr_x, tr_y), (te_x, te_y), cfg)
        for backend in ("ref", "pallas"):
            RESULTS.extend(tunable_bench(workload, backend, trained))

    # the serving claim the CI job gates: at MNIST scale on the pallas
    # datapath a quarter of the clause budget buys >= 2x at <= 2 points
    gate = next(r for r in RESULTS
                if r["workload"] == "mnist-f784"
                and r["backend"] == "pallas" and r["budget"] == 0.25)
    if gate["speedup_vs_full"] < 2.0:
        raise AssertionError(
            f"pallas f=784 budget=25% speedup {gate['speedup_vs_full']:.2f}x"
            " < 2x")
    if gate["accuracy_drop"] > 0.02:
        raise AssertionError(
            f"pallas f=784 budget=25% accuracy drop "
            f"{gate['accuracy_drop'] * 100:.1f} points > 2")

    out_path = os.environ.get("REPRO_BENCH_TUNABLE_JSON",
                              "BENCH_tunable.json")
    payload = {
        "benchmark": "tunable",
        "jax_backend": jax.default_backend(),
        "budgets": list(BUDGETS),
        "results": RESULTS,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path}")
    return payload


if __name__ == "__main__":
    from benchmarks import common

    common.enable_compile_cache()
    main()
