#!/usr/bin/env python3
"""Smoke run of the Tsetlin-machine fleet service on TPU.

Drives the main path — ``TMService``: submit, tick (flush, drain, analysis,
policy), serve — at the full-width deployment of ``configs/tm_mnist.py``
(f=784 booleanized inputs, 10 classes x 64 clauses, int8 TA banks of about
1 MB per tenant) through the compiled Pallas kernels, and holds it bit for
bit to the ``ref`` backend on the same chip.

    python chip_smoke.py             # one chip: K=256 packed fleet + K=1
    python chip_smoke.py --chips 4   # K=512 fleet on a 4-chip mesh vs 1 chip

Data is generated from a seed (``repro.data.mnist``); nothing is downloaded.
Lines starting ``smoke:`` are smoke output, not benchmark metrics. The last
line is one JSON object: ``{"ok": true, "device": {...}}``. Without a TPU,
or when any phase fails, the script exits nonzero and prints no such line.
It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 2023
N_TRAIN, N_TEST = 256, 128   # generated rows: offline/online, held out
EPOCHS = 4                   # offline epochs
CHUNK, CAPACITY = 16, 64     # drain chunk, ring-buffer rows per tenant
ROUNDS = 6                   # online rounds of CHUNK rows to every tenant
ANALYZE_EVERY = 2 * CHUNK    # analysis cadence: every other tick
BUDGET = 0.5                 # pruned serving: half the clauses
LEARNING_BAR = 0.3           # examples/mnist_scale.py's served-accuracy bar
FLEET_ONE_CHIP, FLEET_FOUR_CHIPS = 256, 512


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def smoke(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def require_tpu(n_chips: int):
    """JAX's devices, or a nonzero exit when they are not TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n_chips:
        fail(f"{n_chips} chips wanted, JAX found {len(devs)}")
    return devs


def make_data(side: int):
    from repro.data import mnist

    return mnist.splits(N_TRAIN, N_TEST, seed=SEED, side=side)


def lower_drain(svc) -> tuple[str, float]:
    """The service's drain step as ``tick`` runs it: (StableHLO text,
    seconds to compile it)."""
    import jax
    import jax.numpy as jnp

    from repro.core import online

    want = jnp.full((svc.n_resident,), svc.chunk, jnp.int32)
    keys = jnp.asarray(svc.rng_keys)
    mesh = (contextlib.nullcontext() if svc.mesh is None
            else jax.set_mesh(svc.mesh))
    with mesh:
        lowered = online._consume_many_replicated.lower(
            svc.cfg, svc.chunk, svc.ss, svc.rt, want, keys, monitor=False)
        t = time.perf_counter()
        lowered.compile()
    return lowered.as_text(), time.perf_counter() - t


def run_fleet(cfg, n_tenants: int, data, *, mesh=None,
              inspect: bool = False):
    """One trajectory through the service's public surface: offline
    training, ROUNDS of labelled traffic to every tenant drained by
    ``tick`` (analysis on cadence), then plain and pruned serving.
    Returns (arrays to compare bitwise, smoke stats); ``inspect`` adds
    the drain step's HLO and compile seconds to the stats."""
    import numpy as np

    from repro.configs import tm_mnist
    from repro.core import init_state
    from repro.serve import AdaptPolicy, ServiceConfig, TMService
    from repro.serve.tunable import TunableConfig

    tr_x, tr_y, te_x, te_y = data
    params = tm_mnist.CONFIG
    svc = TMService(cfg, init_state(cfg), ServiceConfig(
        replicas=n_tenants, packed=True, buffer_capacity=CAPACITY,
        chunk=CHUNK, s=params.s_online, T=params.T, seed=SEED, mesh=mesh,
        policy=AdaptPolicy(analyze_every=ANALYZE_EVERY),
        tunable=TunableConfig(),
    ), eval_x=te_x, eval_y=te_y)
    stats = {}
    t = time.perf_counter()
    offline = svc.offline_train(tr_x, tr_y, n_epochs=EPOCHS)
    stats["offline_train_s"] = time.perf_counter() - t
    if inspect:
        stats["drain_hlo"], stats["drain_compile_s"] = lower_drain(svc)

    ticks, analyses = [], 0
    tenant_offset = np.arange(n_tenants) * 7
    for i in range(ROUNDS):
        for j in range(CHUNK):
            idx = (tenant_offset + i * CHUNK + j) % len(tr_x)
            if not svc.submit_rows(tr_x[idx], tr_y[idx]).all():
                fail("the ring buffers refused labelled rows")
        while svc.buffered.any():
            t = time.perf_counter()
            done = svc.tick()
            ticks.append(time.perf_counter() - t)
            analyses += done.accuracy is not None
    stats["first_tick_s"] = ticks[0]
    stats["steady_tick_s"] = statistics.median(ticks[2:] or ticks)
    stats["ticks"], stats["analyses"] = len(ticks), analyses
    if analyses < 1:
        fail(f"K={n_tenants}: no analysis ran on cadence")

    served = svc.serve(te_x)
    svc.calibrate()
    served_budget = svc.serve(te_x, budget=BUDGET)
    acc = (served == np.asarray(te_y)[None]).mean(axis=1)
    stats["served_acc_min"], stats["served_acc_mean"] = acc.min(), acc.mean()
    stats["sharding"] = str(svc.ss.tm.ta_state.sharding)
    stats["memory"] = device_memory(svc.ss.tm.ta_state.sharding.device_set)
    out = {
        "ta_state": np.asarray(svc.ss.tm.ta_state),
        "steps": svc.steps,
        "offline_acc": offline,
        "analysis_acc": np.stack([h[1] for h in svc.history]),
        "served": served,
        "served_budget": served_budget,
    }
    return out, stats


def assert_bitwise(name: str, got: dict, want: dict) -> None:
    import numpy as np

    for key in want:
        if not np.array_equal(got[key], want[key]):
            fail(f"{name}: {key} differs from the oracle run")
    smoke(f"{name}: bitwise equal ({', '.join(want)})")


def report(name: str, stats: dict) -> None:
    if "drain_hlo" in stats:
        kernel = "tpu_custom_call" in stats["drain_hlo"]
        smoke(f"{name}: drain step compiled in "
              f"{stats['drain_compile_s']:.3f} s, compiled Pallas kernel "
              f"{'present' if kernel else 'absent'}")
    smoke(f"{name}: offline_train {stats['offline_train_s']:.3f} s, "
          f"first tick {stats['first_tick_s']:.3f} s, steady tick "
          f"{stats['steady_tick_s']:.4f} s (median of {stats['ticks']}), "
          f"{stats['analyses']} analyses, served accuracy min "
          f"{stats['served_acc_min']:.3f} mean {stats['served_acc_mean']:.3f}")
    smoke(f"{name}: device memory_stats {stats['memory']}")


def device_memory(devs) -> str:
    """Bytes in use now and at the peak so far, per device (the peak
    never resets within a process)."""
    parts = []
    for d in sorted(devs, key=lambda d: d.id):
        mem = d.memory_stats() or {}
        parts.append(f"{d.id}: in_use {mem.get('bytes_in_use')} "
                     f"peak {mem.get('peak_bytes_in_use')}")
    return "; ".join(parts)


def one_chip(cfg, data, n_tenants: int = FLEET_ONE_CHIP) -> str:
    """The K-tenant packed fleet and a K=1 service, each against the same
    trajectory on the ``ref`` backend. Returns the fleet's drain HLO."""
    ref_cfg = dataclasses.replace(cfg, backend="ref")
    fleet, stats = run_fleet(cfg, n_tenants, data, inspect=True)
    report(f"fleet K={n_tenants}", stats)
    if stats["served_acc_min"] <= LEARNING_BAR:
        fail(f"served accuracy {stats['served_acc_min']} is not above "
             f"{LEARNING_BAR}")
    single, stats1 = run_fleet(cfg, 1, data)
    report("K=1", stats1)
    oracle, _ = run_fleet(ref_cfg, n_tenants, data)
    assert_bitwise(f"fleet K={n_tenants} vs ref", fleet, oracle)
    oracle1, _ = run_fleet(ref_cfg, 1, data)
    assert_bitwise("K=1 vs ref", single, oracle1)
    return stats["drain_hlo"]


def four_chips(cfg, data, devs, n_tenants: int = FLEET_FOUR_CHIPS) -> str:
    """The fleet's tenant axis sharded over a 4-chip mesh, against the same
    fleet without a mesh on the first chip. Returns the mesh drain's HLO."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devs[:4]), ("data",))
    sharded, stats = run_fleet(cfg, n_tenants, data, mesh=mesh,
                               inspect=True)
    report(f"fleet K={n_tenants} on a {len(devs[:4])}-chip mesh", stats)
    smoke(f"plane sharding {stats['sharding']}")
    single, stats1 = run_fleet(cfg, n_tenants, data)
    report(f"fleet K={n_tenants} on one chip", stats1)
    assert_bitwise(f"fleet K={n_tenants} mesh vs one chip", sharded, single)
    return stats["drain_hlo"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        fail("no src/repro beside this script: run it from a checkout")
    sys.path.insert(0, os.path.join(HERE, "src"))

    from repro.configs import tm_mnist
    from repro.kernels import dispatch
    from repro.launch import compile_cache

    devs = require_tpu(args.chips)
    smoke(f"compile cache {compile_cache.enable(HERE)}")
    import jax

    d = devs[0]
    smoke(f"device {d.platform} {d.device_kind} x{len(devs)}")
    kb = dispatch.resolve("auto")
    if kb.name != "pallas" or kb.interpret:
        fail(f"auto resolved to {kb.name} (interpret={kb.interpret})")
    cfg = tm_mnist.CONFIG.tm
    data = make_data(tm_mnist.SIDE)
    t = time.perf_counter()
    if args.chips == 1:
        hlo = one_chip(cfg, data)
    else:
        hlo = four_chips(cfg, data, devs)
    if "tpu_custom_call" not in hlo:
        fail("the drain step holds no compiled Pallas kernel")
    smoke(f"phases took {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
