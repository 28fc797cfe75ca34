"""Fleet ingress benchmark: routed batch ingress vs per-point offers.

Measures the BatchRouter path (``TMService.submit`` staging + packed
``[K, B_ingress]`` block flushes — one jitted dispatch per flush) against
the pre-redesign per-point path (one jitted enqueue dispatch per
datapoint, transcribed below), asserting the ring buffers land bitwise
identical under both. This is the ROADMAP's "Fleet-scale ingress" item:
heavy-traffic serving is dispatch-bound on the producer side, so the win
is roughly the ingress block size.

Machine-readable results go to ``BENCH_ingress.json`` (override with env
``REPRO_BENCH_INGRESS_JSON``). The headline field is
``results[ingress_routed].speedup`` — routed offers/s must stay >= 4x
over the looped per-point path at K = 8 (gated in CI).
"""
from __future__ import annotations

import json
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import init_runtime, init_state
from repro.data import iris
from repro.serve import ServiceConfig, TMService

CFG = common.CFG

RESULTS: list[dict] = []


@partial(jax.jit, static_argnums=0)
def _offer_point(cfg, ss, xs, ys, mask):
    """The pre-redesign ingress: ONE device dispatch per datapoint."""
    from repro.data import buffer as buf_mod

    def push_one(buf_r, x, y, m):
        new_buf, ok = buf_mod.push(buf_r, x, y)
        buf = jax.tree.map(lambda a, b: jnp.where(m, a, b), new_buf, buf_r)
        return buf, ok & m

    bufs, oks = jax.vmap(push_one)(ss.buf, xs, ys, mask)
    return ss._replace(buf=bufs), oks


def ingress_bench(K: int = 8, n_points: int = 256, block: int = 32,
                  trials: int = 5, *, cfg=None, data=None, rt=None) -> dict:
    """offers/s: routed staging+flush vs per-point dispatch; bitwise check.

    Defaults measure the iris machine; ``cfg``/``data=(xs, ys)``/``rt``
    parameterize the same protocol over other workloads (benchmarks/scale.py
    runs it at MNIST widths) so the per-point baseline lives in ONE place.
    Overriding ``cfg`` requires ``rt`` — the default runtime's s/T are
    iris-calibrated and would silently miscalibrate another machine.
    """
    if cfg is not None and rt is None:
        raise ValueError("pass rt= when overriding cfg= (default s/T are "
                         "iris-calibrated)")
    cfg = CFG if cfg is None else cfg
    xs, ys = iris.load() if data is None else data
    rt = init_runtime(cfg, s=3.0, T=15) if rt is None else rt
    # distinct per-replica streams (row rotations), n_points each
    rows = np.stack([np.roll(np.arange(len(xs)), -7 * r)[
        np.arange(n_points) % len(xs)] for r in range(K)])   # [K, n]
    feed_x = np.asarray(xs)[rows]                            # [K, n, f]
    feed_y = np.asarray(ys)[rows].astype(np.int32)           # [K, n]
    full_mask = jnp.ones((K,), dtype=bool)

    def make_service():
        return TMService(cfg, init_state(cfg), ServiceConfig(
            replicas=K, buffer_capacity=n_points, chunk=16,
            ingress_block=block, seed=list(range(K)),
        ), rt=rt)

    def run_routed(svc):
        for i in range(n_points):
            svc.submit_rows(feed_x[:, i], feed_y[:, i])
        svc.flush()
        jax.block_until_ready(svc.ss.buf.data_x)

    def run_per_point(svc):
        ss = svc.ss
        for i in range(n_points):
            ss, _ = _offer_point(cfg, ss, jnp.asarray(feed_x[:, i]),
                                 jnp.asarray(feed_y[:, i]), full_mask)
        svc.ss = ss
        jax.block_until_ready(svc.ss.buf.data_x)

    # warm both paths (compile) + bitwise equivalence of the landed buffers
    warm_r, warm_p = make_service(), make_service()
    run_routed(warm_r)
    run_per_point(warm_p)
    for name in ("data_x", "data_y", "head", "size"):
        a = np.asarray(getattr(warm_r.ss.buf, name))
        b = np.asarray(getattr(warm_p.ss.buf, name))
        if not np.array_equal(a, b):
            raise AssertionError(
                f"routed ingress diverged from per-point offers ({name})"
            )

    # timed: interleave so background host load skews both paths equally
    t_routed, t_point = float("inf"), float("inf")
    for _ in range(trials):
        svc = make_service()
        t0 = time.perf_counter()
        run_routed(svc)
        t_routed = min(t_routed, time.perf_counter() - t0)

        svc = make_service()
        t0 = time.perf_counter()
        run_per_point(svc)
        t_point = min(t_point, time.perf_counter() - t0)

    offers = K * n_points
    return {
        "n_replicas": K,
        "points_per_replica": n_points,
        "ingress_block": block,
        "wall_s_routed": t_routed,
        "wall_s_per_point": t_point,
        "speedup": t_point / t_routed,
        "offers_per_s_routed": offers / t_routed,
        "offers_per_s_per_point": offers / t_point,
        "device_dispatches_routed": int(np.ceil(n_points / block)),
        "device_dispatches_per_point": n_points,
        "bitwise_identical": True,
    }


def main():
    RESULTS.clear()
    for K in (2, 8):
        row = ingress_bench(K=K)
        name = "ingress_routed" if K == 8 else f"ingress_routed_k{K}"
        print(
            f"{name},{row['wall_s_routed'] * 1e6:.1f},"
            f"K={K};points={row['points_per_replica']};"
            f"offers_per_s={row['offers_per_s_routed']:.0f};"
            f"per_point_s={row['wall_s_per_point']:.4f};"
            f"speedup={row['speedup']:.2f}x;bitwise_identical=1"
        )
        RESULTS.append({"name": name, **row})

    out_path = os.environ.get("REPRO_BENCH_INGRESS_JSON",
                              "BENCH_ingress.json")
    payload = {
        "benchmark": "ingress",
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
