"""Set-up of one cell: its files, its data, the service under test.

Everything a configuration or a traffic mix holds lives in its own data file
(``bench/configs/<name>.json``, ``bench/traffic/<name>.json``), found by the
name ``BENCHMARK.json`` gives it; nothing here names a cell.
"""
from __future__ import annotations

import json
import os

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read(os.path.join(ROOT, "BENCHMARK.json"))


def load_config(name: str) -> dict:
    return _read(os.path.join(BENCH, "configs", f"{name}.json"))


def load_traffic(name: str) -> dict:
    return _read(os.path.join(BENCH, "traffic", f"{name}.json"))


def cell(workload: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic mix) of a cell by name."""
    bm = benchmark()
    for w in bm["workloads"]:
        if w["name"] == workload:
            return w, load_config(w["config"]), load_traffic(w["traffic"])
    raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")


def seed32(seed: int, salt: int) -> int:
    """A 31-bit seed derived from the run's seed (which may exceed 32 bits)."""
    return int(np.random.SeedSequence([seed, salt]).generate_state(1)[0]
               & 0x7FFFFFFF)


def tm_config(conf: dict):
    from repro.core.tm import TMConfig

    return TMConfig(**conf["machine"])


def make_data(conf: dict, seed: int) -> dict:
    """Host arrays from the seed: ``base`` rows for the pre-trained bank,
    ``eval`` rows for the policy's analysis, ``pool`` rows for traffic, cut
    in that order from the shuffled table (``base_gap`` rows skipped after
    the base rows)."""
    d = conf["data"]
    if d["kind"] != "iris":
        raise SystemExit(f"bench: unknown data kind {d['kind']!r}")
    from bench.data import iris

    xs, ys = iris.load(seed=seed)
    a = d["base_rows"]
    b = a + d.get("base_gap", 0)
    c = b + d["pool_rows"]
    return {
        "base": (xs[:a], ys[:a]),
        "pool": (xs[b:c], ys[b:c]),
        "eval": (xs[c:c + d["eval_rows"]], ys[c:c + d["eval_rows"]]),
    }


def build_service(conf: dict, base_bank, eval_xy, seed: int):
    """The program's ``TMService`` for this configuration, every tenant
    starting from ``base_bank``."""
    from repro.core.tm import TMState
    from repro.serve import AdaptPolicy, ServiceConfig, TMService

    sc = conf["service"]
    return TMService(tm_config(conf), TMState(ta_state=base_bank),
                     ServiceConfig(
        replicas=conf["tenants"], packed=sc["packed"],
        buffer_capacity=sc["buffer_capacity"], chunk=sc["chunk"],
        ingress_block=sc["ingress_block"], s=conf["s_online"], T=conf["T"],
        seed=seed,
        policy=AdaptPolicy(analyze_every=sc["analyze_every"],
                           rollback_threshold=sc["rollback_threshold"]),
    ), eval_x=eval_xy[0], eval_y=eval_xy[1])
