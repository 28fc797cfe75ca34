"""Set-up of one cell: its files, its data, the service under test.

Everything a configuration or a traffic mix holds lives in its own data file
(``bench/configs/<name>.json``, ``bench/traffic/<name>.json``), found by the
name ``BENCHMARK.json`` gives it. A configuration's file names its rows'
generator (``data.kind``: ``bench/data/<kind>.py``) and its plain reference
(``reference``: ``bench/<name>.py``, ``bench/reference.py`` by default);
nothing here names a cell or a configuration.
"""
from __future__ import annotations

import importlib
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


def _member(package: str, name: str, attr: str, what: str):
    """``attr`` of the module ``<package>.<name>`` under ``bench/``; a name
    with no such file, or a file without ``attr``, exits naming the file."""
    rel = "/".join(["bench", *package.split(".")[1:], f"{name}.py"])
    if not name.isidentifier():
        raise SystemExit(f"bench: {what} {name!r} names no file ({rel})")
    try:
        mod = importlib.import_module(f"{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{package}.{name}":
            raise
        raise SystemExit(f"bench: unknown {what} {name!r}: no {rel}") from None
    if not hasattr(mod, attr):
        raise SystemExit(f"bench: {what} {name!r}: {rel} has no {attr}")
    return getattr(mod, attr)


SPLIT = ("base_rows", "base_gap", "pool_rows", "eval_rows")


def make_data(conf: dict, seed: int) -> dict:
    """Host arrays from the seed: ``base`` rows for the pre-trained bank,
    ``eval`` rows for the policy's analysis, ``pool`` rows for traffic, cut
    in that order from the shuffled rows (``base_gap`` rows skipped after
    the base rows). The rows come from ``bench/data/<kind>.py``'s
    ``load(seed, **opts)``, ``opts`` being the ``data`` keys other than
    ``kind`` and the split's."""
    d = conf["data"]
    opts = {k: v for k, v in d.items() if k != "kind" and k not in SPLIT}
    load = _member("bench.data", d["kind"], "load", "data kind")
    xs, ys = load(seed=seed, **opts)
    a = d["base_rows"]
    b = a + d.get("base_gap", 0)
    c = b + d["pool_rows"]
    if len(xs) < c + d["eval_rows"]:
        raise SystemExit(f"bench: data kind {d['kind']!r} gave {len(xs)} "
                         f"rows; the split needs {c + d['eval_rows']}")
    return {
        "base": (xs[:a], ys[:a]),
        "pool": (xs[b:c], ys[b:c]),
        "eval": (xs[c:c + d["eval_rows"]], ys[c:c + d["eval_rows"]]),
    }


def reference(conf: dict):
    """The configuration's ``Reference`` class, from ``bench/<name>.py``
    where ``name`` is its ``reference`` key (``reference`` when absent)."""
    return _member("bench", conf.get("reference", "reference"), "Reference",
                   "reference")


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
