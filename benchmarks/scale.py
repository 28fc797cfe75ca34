"""Datapath-width scaling benchmark: the scale path from iris to MNIST.

Every other number in this repo is measured at iris width (f=16 boolean
inputs). The paper's architecture is motivated by edge workloads where the
datapath *width* dominates, so this benchmark re-measures the three hot
paths on the generated booleanized digit workload at

    f in {16, 196, 784}   (4x4 / 14x14 / 28x28 rasters; 784 = MNIST width)

and asserts the ROADMAP's scaling prediction: the batch-first and
replica-parallel paths must *widen* their advantage as f grows —

* ``scale_batch_infer_f*`` — batched GEMM inference vs the legacy
  vmap-of-per-sample plane (bitwise-equal predictions asserted). The
  batch-first headline: the GEMM's one-pass include-bank streaming wins
  more as the literal axis grows.
* ``scale_sweep_f*`` — the replica-parallel sweep engine vs the legacy
  vmap-of-scan ``grid_search_device`` (bitwise-equal accuracies
  asserted; the protocol is ``benchmarks.crossval.sweep_bench``
  parameterized over width). The replicated headline: factored
  uniforms/literals are stored once per data stream, and the per-point
  draw volume the legacy path re-materializes grows with f.
* ``scale_fleet_drain_f*`` / ``scale_ingress_f*`` — fleet drain vs K
  serial sessions, routed ingress vs per-point offers (both
  bitwise-asserted; the protocols are ``benchmarks.fleet.drain_bench``
  and ``benchmarks.ingress.ingress_bench`` parameterized over width) —
  the ROADMAP "wire serving + ingress to a bigger workload" item.
* ``scale_packed_infer_f*`` — the §13 bit-packed datapath vs the boolean
  one: batched inference on uint32 word rows (AND+popcount kernels)
  against the same pass on bool rows, per backend, bitwise-equal
  predictions asserted. Reports the bandwidth story alongside the wall
  clock: bytes per stored row and the ring-buffer / ingress-staging
  footprints at the fleet-bench serving geometry. Gated in-script AND
  in CI: packed must be >= 2x unpacked at f=784 on the pallas backend.
* ``scale_parity_f*`` — one sweep cell (offline epochs + analysis) and
  one batched inference pass run under BOTH kernel backends (ref and
  pallas-interpret), asserted bitwise identical at every width.

The widening asserts (f=784 speedup >= f=16 speedup for the batch-first
and replicated rows) run inside this script AND as a CI gate over the
machine-readable output, ``BENCH_scale.json`` (override with env
``REPRO_BENCH_SCALE_JSON``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.crossval import _min_time, sweep_bench as _sweep_bench
from benchmarks.fleet import drain_bench as _drain_bench
from benchmarks.ingress import ingress_bench as _ingress_bench
from repro.configs import tm_mnist
from repro.core import init_runtime, init_state
from repro.core import tm as tm_mod
from repro.data import blocks, mnist

RESULTS: list[dict] = []

SIDES = (4, 14, 28)            # f = 16 / 196 / 784
S_GRID = (2.0, 3.0)
T_GRID = (32,)
N_EPOCHS = 2
N_ORDERINGS = 2


def _emit(name: str, us_per_call: float, derived: str, **extra):
    print(f"{name},{us_per_call:.1f},{derived}")
    RESULTS.append({"name": name, "us_per_call": us_per_call, **extra})


@functools.lru_cache(maxsize=None)
def _width(side: int):
    """(cfg, system params, xs, ys) for one raster width — cached so the
    five bench functions per side share one generated dataset (rendering
    is pure and seed-deterministic; every consumer reads it immutably)."""
    params = tm_mnist.config_for_side(side)
    xs, ys = mnist.load(side=side)
    return params.tm, params, xs, ys


def batch_infer_bench(side: int, trials: int = 5) -> dict:
    """Batched GEMM inference vs the legacy vmap plane; bitwise asserted."""
    cfg, params, xs, ys = _width(side)
    rt = init_runtime(cfg, s=params.s_offline, T=params.T)
    st = init_state(cfg, jax.random.PRNGKey(0))
    xs_j = jnp.asarray(xs)

    infer_batch = jax.jit(lambda s, x: tm_mod.predict_batch(cfg, s, rt, x))
    infer_vmap = jax.jit(
        lambda s, x: jax.vmap(lambda r: tm_mod.predict(cfg, s, rt, r))(x)
    )
    # Interleave trials so background host load skews both paths equally.
    dt_b, dt_v = float("inf"), float("inf")
    preds_b = preds_v = None
    for _ in range(trials):
        t, preds_b = _min_time(lambda: infer_batch(st, xs_j), trials=1)
        dt_b = min(dt_b, t)
        t, preds_v = _min_time(lambda: infer_vmap(st, xs_j), trials=1)
        dt_v = min(dt_v, t)
    if not np.array_equal(np.asarray(preds_b), np.asarray(preds_v)):
        raise AssertionError(
            f"batched and vmap inference diverge at f={cfg.n_features}"
        )
    return {
        "f": cfg.n_features,
        "batch": len(xs),
        "wall_s_batch": dt_b,
        "wall_s_vmap": dt_v,
        "speedup": dt_v / dt_b,
        "datapoints_per_s": len(xs) / dt_b,
        "bitwise_identical": True,
    }


def packed_infer_bench(side: int, trials: int = 5, K: int = 4,
                       cap: int = 32, block: int = 32) -> dict:
    """Packed (AND+popcount, §13) vs boolean batch inference per backend.

    Both paths enter through the same ``predict_batch`` — the uint32 dtype
    routes rows to the packed kernels — so this measures exactly what a
    ``ServiceConfig(packed=True)`` service runs in steady state: inference
    straight off word-packed rows, no unpack. The headline is the pallas
    backend, where packing shrinks the word grid ~32x; the ref backend's
    ratio is reported too (its unpacked path is already a dense int GEMM,
    so popcount is not expected to win there at width). K/cap/block pin
    the fleet-bench serving geometry for the memory-footprint rows.
    """
    from repro.kernels import packing

    _, params, xs, ys = _width(side)
    f = params.tm.n_features
    xs_j = jnp.asarray(xs)
    xp = packing.pack_bits(xs_j)

    row: dict = {"f": f, "batch": len(xs)}
    for backend in ("pallas", "ref"):
        cfg = dataclasses.replace(params.tm, backend=backend)
        rt = init_runtime(cfg, s=params.s_offline, T=params.T)
        st = init_state(cfg, jax.random.PRNGKey(0))
        infer = jax.jit(lambda s, x: tm_mod.predict_batch(cfg, s, rt, x))
        # Interleave trials so background host load skews both paths
        # equally (same protocol as batch_infer_bench).
        dt_u, dt_p = float("inf"), float("inf")
        preds_u = preds_p = None
        for _ in range(trials):
            t, preds_u = _min_time(lambda: infer(st, xs_j), trials=1)
            dt_u = min(dt_u, t)
            t, preds_p = _min_time(lambda: infer(st, xp), trials=1)
            dt_p = min(dt_p, t)
        if not np.array_equal(np.asarray(preds_u), np.asarray(preds_p)):
            raise AssertionError(
                f"packed and unpacked inference diverge at f={f} "
                f"on the {backend} backend"
            )
        row[f"wall_s_unpacked_{backend}"] = dt_u
        row[f"wall_s_packed_{backend}"] = dt_p
        row[f"speedup_{backend}"] = dt_u / dt_p

    bpp_unpacked = f                            # bool row: 1 byte/literal
    bpp_packed = packing.packed_row_bytes(f)    # 4 * ceil(f/32)
    row.update({
        "speedup": row["speedup_pallas"],       # the gated headline
        "datapoints_per_s": len(xs) / row["wall_s_packed_pallas"],
        "bytes_per_point_unpacked": bpp_unpacked,
        "bytes_per_point_packed": bpp_packed,
        "bandwidth_reduction": bpp_unpacked / bpp_packed,
        "buffer_bytes_unpacked": K * cap * bpp_unpacked,
        "buffer_bytes_packed": K * cap * bpp_packed,
        "staging_bytes_unpacked": K * block * bpp_unpacked,
        "staging_bytes_packed": K * block * bpp_packed,
        "bitwise_identical": True,
    })
    return row


def sweep_bench(side: int) -> dict:
    cfg, params, xs, ys = _width(side)
    osets, _ = blocks.paper_sets(xs, ys, N_ORDERINGS)
    row = _sweep_bench(
        N_ORDERINGS, cfg=cfg, osets=osets,
        s_values=S_GRID, T_values=T_GRID, n_epochs=N_EPOCHS,
    )
    return {"f": cfg.n_features, **row}


def fleet_drain_bench(side: int, K: int = 4, cap: int = 32,
                      chunk: int = 8) -> dict:
    cfg, params, xs, ys = _width(side)
    rt = init_runtime(cfg, s=params.s_online, T=params.T)
    row = _drain_bench(K=K, cap=cap, chunk=chunk, trials=3,
                       cfg=cfg, data=(xs, ys), rt=rt)
    return {"f": cfg.n_features, **row}


def ingress_bench(side: int, K: int = 4, n_points: int = 96,
                  block: int = 32) -> dict:
    cfg, params, xs, ys = _width(side)
    rt = init_runtime(cfg, s=params.s_online, T=params.T)
    row = _ingress_bench(K=K, n_points=n_points, block=block, trials=3,
                         cfg=cfg, data=(xs, ys), rt=rt)
    return {"f": cfg.n_features, **row}


def parity_bench(side: int, seed: int = 0) -> dict:
    """One sweep cell + one batched inference pass under both backends,
    asserted bitwise identical at this width."""
    from repro.core import accuracy as acc_mod
    from repro.core import feedback as fb_mod

    _, params, xs, ys = _width(side)
    outs = {}
    for backend in ("ref", "pallas"):
        cfg = dataclasses.replace(params.tm, backend=backend)
        rt = init_runtime(cfg, s=params.s_offline, T=params.T)
        st = fb_mod.train_epochs(
            cfg, init_state(cfg), rt, jnp.asarray(xs[:20]),
            jnp.asarray(ys[:20]), jax.random.PRNGKey(seed), 1,
        )
        acc = acc_mod.analyze(cfg, st, rt, jnp.asarray(xs[20:60]),
                              jnp.asarray(ys[20:60]))
        preds = tm_mod.predict_batch(cfg, st, rt, jnp.asarray(xs[60:120]))
        outs[backend] = (np.asarray(st.ta_state), float(acc),
                         np.asarray(preds))
    ta_ok = np.array_equal(outs["ref"][0], outs["pallas"][0])
    acc_ok = outs["ref"][1] == outs["pallas"][1]
    pred_ok = np.array_equal(outs["ref"][2], outs["pallas"][2])
    if not (ta_ok and acc_ok and pred_ok):
        raise AssertionError(
            f"ref<->pallas parity broken at f={side * side}: "
            f"ta={ta_ok} acc={acc_ok} preds={pred_ok}"
        )
    return {
        "f": side * side,
        "accuracy": outs["ref"][1],
        "bitwise_identical": True,
    }


def main():
    RESULTS.clear()
    by_metric: dict[str, dict[int, dict]] = {}

    for side in SIDES:
        f = side * side
        for metric, fn in (
            ("scale_batch_infer", batch_infer_bench),
            ("scale_packed_infer", packed_infer_bench),
            ("scale_sweep", sweep_bench),
            ("scale_fleet_drain", fleet_drain_bench),
            ("scale_ingress", ingress_bench),
            ("scale_parity", parity_bench),
        ):
            row = fn(side)
            by_metric.setdefault(metric, {})[f] = row
            name = f"{metric}_f{f}"
            us = next(
                (row[k] * 1e6 for k in
                 ("wall_s_batch", "wall_s_packed_pallas", "wall_s_engine",
                  "wall_s_fleet", "wall_s_routed") if k in row), 0.0,
            )
            derived = ";".join(
                f"{k}={row[k]:.3g}" if isinstance(row[k], float)
                else f"{k}={row[k]}"
                for k in row
            )
            _emit(name, us, derived, **row)

    # The ROADMAP scaling prediction, asserted: the batch-first and
    # replicated paths widen their advantage from iris width to MNIST
    # width (the CI gate re-checks this over the JSON artifact).
    for metric in ("scale_batch_infer", "scale_sweep"):
        lo = by_metric[metric][16]["speedup"]
        hi = by_metric[metric][784]["speedup"]
        if hi < lo:
            raise AssertionError(
                f"{metric}: f=784 speedup {hi:.2f}x < f=16 speedup "
                f"{lo:.2f}x — the scale path narrowed its advantage"
            )
        print(f"# {metric}: f16 {lo:.2f}x -> f784 {hi:.2f}x (widened)")

    # The §13 packed-datapath gate (the CI gate re-checks this over the
    # JSON artifact): at full MNIST width the AND+popcount kernels must
    # beat the boolean path by >= 2x on the pallas backend.
    pk = by_metric["scale_packed_infer"][784]
    if pk["speedup_pallas"] < 2.0:
        raise AssertionError(
            f"scale_packed_infer: f=784 pallas packed speedup "
            f"{pk['speedup_pallas']:.2f}x < 2x — the packed datapath "
            f"lost its word-grid advantage"
        )
    print(
        f"# scale_packed_infer: f784 pallas packed "
        f"{pk['speedup_pallas']:.2f}x unpacked (gate >= 2x), "
        f"{pk['bandwidth_reduction']:.1f}x fewer bytes/point"
    )

    out_path = os.environ.get("REPRO_BENCH_SCALE_JSON", "BENCH_scale.json")
    payload = {
        "benchmark": "scale",
        "jax_backend": jax.default_backend(),
        "sides": list(SIDES),
        "grid": {"s": list(S_GRID), "T": list(T_GRID), "n_epochs": N_EPOCHS,
                 "n_orderings": N_ORDERINGS},
        "results": RESULTS,
    }
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)
    print(f"# wrote {out_path}")
    return payload


if __name__ == "__main__":
    from benchmarks import common

    common.enable_compile_cache()
    main()
