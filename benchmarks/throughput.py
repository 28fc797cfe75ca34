"""Paper §6: performance — parallel TM datapath + hyperparameter search.

The FPGA updates all clauses/TAs in 2 clock cycles, one datapoint per clock.
The TPU/JAX analogue measured here:

* `tm_train_step`  — fused inference+feedback for ONE datapoint (all
  C x J x 2f TA lanes in parallel): wall time per datapoint + TA-updates/s.
* `tm_infer_batch` — batch-first inference throughput (datapoints/s) on the
  dispatched `clause_eval_batch_replicated` path at R = 1 (include bank
  read once per batch).
* `tm_infer_vmap`  — the legacy vmap-of-per-sample inference plane, kept as
  the baseline the batch path is tracked against (bitwise-equal predictions
  asserted every run).
* `tm_online_drain` — chunked drain rate of one machine (`OnlineSession`,
  the fleet drain `online._consume_many_replicated` at K = 1) vs the
  one-jitted-call-per-datapoint serving loop it replaced.
* `hpsearch_grid`  — the paper's goal (ii): a (s x T x orderings) grid as a
  single vmapped program vs. the same grid run sequentially; the speedup is
  the replication-parallelism the FPGA gets from spatial hardware.
* `activity`       — fraction of TA lanes that actually flip per step (the
  clock-gating/energy analogue; lower s => sparser feedback => lower power,
  §5.1's "bias away from issuing feedback").

Every row is also written machine-readable to ``BENCH_throughput.json``
(override with env ``REPRO_BENCH_JSON``) so speedups are tracked across PRs.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import feedback as fb
from repro.core import hpsearch
from repro.core import online as online_mod
from repro.core import tm as tm_mod
from repro.data import blocks, iris

CFG = common.CFG

RESULTS: list[dict] = []


def _time(fn, *args, n=5, warmup=2):
    """Mean seconds/call over n calls (after warmup). Comparisons between
    two paths should interleave repeated _time calls and take each path's
    min — see the batched-vs-vmap inference block."""
    for _ in range(warmup):
        out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n, out


def _emit(name: str, us_per_call: float, derived: str, **extra):
    """Print the CSV row (run.py contract) and record the JSON row."""
    print(f"{name},{us_per_call:.1f},{derived}")
    RESULTS.append({"name": name, "us_per_call": us_per_call, **extra})


def main():
    RESULTS.clear()
    xs, ys = iris.load()
    xs_j, ys_j = jnp.asarray(xs), jnp.asarray(ys)
    rt = tm_mod.init_runtime(CFG, s=1.375, T=15)
    st = tm_mod.init_state(CFG, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)

    # --- single-datapoint fused train step (the 2-cycle datapath) ---
    step = jax.jit(lambda s, x, y, k: fb.train_step(CFG, s, rt, x, y, k))
    dt, _ = _time(step, st, xs_j[0], ys_j[0], key, n=20)
    ta_lanes = CFG.max_classes * CFG.max_clauses * CFG.n_literals
    _emit("tm_train_step", dt * 1e6,
          f"datapoints_per_s={1/dt:.0f};ta_lanes_per_step={ta_lanes};"
          f"ta_updates_per_s={ta_lanes/dt:.2e}",
          datapoints_per_s=1 / dt, ta_updates_per_s=ta_lanes / dt)

    # --- streamed epoch (150 datapoints serially, hardware row order) ---
    epoch = jax.jit(lambda s, k: fb.train_datapoints(CFG, s, rt, xs_j, ys_j, k))
    dt, (_, aux) = _time(epoch, st, key, n=3)
    _emit("tm_train_epoch150", dt * 1e6, f"datapoints_per_s={150/dt:.0f}",
          datapoints_per_s=150 / dt)

    # --- inference: batch-first dispatched path vs legacy vmap plane ---
    infer_batch = jax.jit(lambda s, x: tm_mod.predict_batch(CFG, s, rt, x))
    infer_vmap = jax.jit(
        lambda s, x: jax.vmap(lambda r: tm_mod.predict(CFG, s, rt, r))(x)
    )
    # Interleave the trials: background load on the host then skews both
    # paths equally instead of whichever happened to run second.
    dt_b, dt_v = float("inf"), float("inf")
    preds_b = preds_v = None
    for _ in range(6):
        t, preds_b = _time(infer_batch, st, xs_j, n=200, warmup=5)
        dt_b = min(dt_b, t)
        t, preds_v = _time(infer_vmap, st, xs_j, n=200, warmup=5)
        dt_v = min(dt_v, t)
    if not np.array_equal(np.asarray(preds_b), np.asarray(preds_v)):
        raise AssertionError("batched and vmap inference predictions diverge")
    speedup = dt_v / dt_b
    _emit("tm_infer_batch150", dt_b * 1e6,
          f"datapoints_per_s={150/dt_b:.0f}", datapoints_per_s=150 / dt_b)
    _emit("tm_infer_vmap150", dt_v * 1e6,
          f"datapoints_per_s={150/dt_v:.0f};batched_speedup={speedup:.2f}x;"
          f"bitwise_identical=1",
          datapoints_per_s=150 / dt_v, batched_speedup=speedup,
          bitwise_identical=True)

    # --- online serving drain: chunked drain vs per-point consume ---
    def drain(chunk):
        sess = online_mod.OnlineSession(
            CFG, st, rt, buffer_capacity=128, chunk=chunk, seed=0
        )
        for i in range(128):
            sess.offer(xs[i % 150], int(ys[i % 150]))
        t0 = time.perf_counter()
        n = sess.learn_available(128)
        jax.block_until_ready(sess.ss.tm.ta_state)
        return (time.perf_counter() - t0) / max(n, 1)

    drain(16), drain(1)  # warm both traces so compile time stays untimed
    per_pt_chunked = drain(16)
    per_pt_single = drain(1)
    _emit("tm_online_drain128", per_pt_chunked * 1e6,
          f"datapoints_per_s={1/per_pt_chunked:.0f};"
          f"chunked_speedup={per_pt_single/per_pt_chunked:.2f}x",
          datapoints_per_s=1 / per_pt_chunked,
          chunked_speedup=per_pt_single / per_pt_chunked)

    # --- activity factor vs s (energy analogue), both s-policies ---
    # The paper: lower s => "bias away from issuing feedback" => lower power.
    # That holds under the `hardware` policy (all stochastic events ~ (s-1)/s)
    # and INVERTS under the software `standard` policy (erase ~ 1/s) — the
    # calibration evidence for DESIGN.md §2's s-semantics discussion.
    import dataclasses as _dc

    for policy in ("standard", "hardware"):
        cfgp = _dc.replace(CFG, s_policy=policy, boost_true_positive=False)
        parts = []
        activities = {}
        for s_val in (1.0, 1.375, 4.0):
            rt_s = tm_mod.init_runtime(cfgp, s=s_val, T=15)
            st2, aux = jax.jit(
                lambda s, k: fb.train_datapoints(cfgp, s, rt_s, xs_j, ys_j, k)
            )(st, key)
            act = float(np.mean(np.asarray(aux.activity)))
            parts.append(f"s={s_val}:{act:.4f}")
            activities[str(s_val)] = act
        _emit(f"tm_activity_vs_s_{policy}", 0.0, ";".join(parts),
              activity_by_s=activities)

    # --- hyperparameter-search acceleration (goal ii) ---
    osets, _ = blocks.iris_paper_sets(n_orderings=12)
    s_grid = [1.375, 2.0, 3.0, 4.0]
    T_grid = [5, 10, 15]
    t0 = time.time()
    res = hpsearch.grid_search(
        CFG, s_grid, T_grid,
        osets.offline_x, osets.offline_y,
        osets.validation_x, osets.validation_y,
        n_epochs=10,
    )
    jax.block_until_ready(res.val_accuracy)
    t_vmapped = time.time() - t0

    # sequential reference: one grid cell at a time (amortised estimate over
    # a subsample to keep CPU wall time sane)
    t0 = time.time()
    _ = hpsearch.grid_search(
        CFG, s_grid[:1], T_grid[:1],
        osets.offline_x[:1], osets.offline_y[:1],
        osets.validation_x[:1], osets.validation_y[:1],
        n_epochs=10,
    )
    t_one = (time.time() - t0)
    n_cells = len(s_grid) * len(T_grid) * 12
    best_s, best_T, best_acc = hpsearch.best(res)
    _emit("hpsearch_grid", t_vmapped * 1e6,
          f"cells={n_cells};vmapped_s={t_vmapped:.2f};"
          f"seq_est_s={t_one*n_cells:.2f};"
          f"speedup={t_one*n_cells/max(t_vmapped,1e-9):.1f}x;"
          f"best_s={best_s};best_T={best_T};best_val={best_acc:.3f}",
          cells=n_cells, vmapped_s=t_vmapped, seq_est_s=t_one * n_cells,
          speedup=t_one * n_cells / max(t_vmapped, 1e-9))

    out_path = os.environ.get("REPRO_BENCH_JSON", "BENCH_throughput.json")
    payload = {
        "benchmark": "throughput",
        "backend": CFG.backend,
        "jax_backend": jax.default_backend(),
        "results": RESULTS,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_path}")


if __name__ == "__main__":
    common.enable_compile_cache()
    main()
