#!/usr/bin/env python3
"""The chip benchmark of ``TMService``: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``). A run makes its data and the tenants'
pre-trained bank from ``--seed``, builds the service, warms every shape the
window uses (set-up), drives the service's public surface for ``--seconds``
(the window), then checks what the window produced against the plain
reference the configuration names (``bench/reference.py`` unless its
``reference`` key names another) for tenants drawn from the seed. With
``--trace 0`` the last line reports the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the line reports its per-layer
metrics, each read by ``bench/metrics/<metric>.py``.

Without a TPU, or with fewer chips than the cell asks for, it exits nonzero
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import loops, setup, work  # noqa: E402


class NoChip(SystemExit):
    pass


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def require_devices(chips: int, platform: str = "tpu"):
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"bench: JAX found no {platform.upper()} "
                     f"(platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


def enable_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache`` (a
    fixed path), or where ``JAX_COMPILATION_CACHE_DIR`` points; every
    program is cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# The check against the plain reference
# ---------------------------------------------------------------------------


def tenant_keys(svc_seed: int, rids):
    import jax

    base = jax.random.PRNGKey(svc_seed)
    return np.stack([np.asarray(jax.random.fold_in(base, int(r)))
                     for r in rids])


def compare(ref_out: dict, prog: dict) -> dict:
    """Numbers compared, each an exact count with the limit 0."""
    acc_p = np.asarray(prog["acc"], np.float32)
    acc_r = np.asarray(ref_out["acc"], np.float32)
    if acc_p.shape == acc_r.shape:
        acc_mm = int(np.sum(acc_p != acc_r))
    else:
        acc_mm = int(max(acc_p.size, acc_r.size, 1))
    return {
        "bank_mismatch": int(np.sum(prog["banks"] != ref_out["banks"])),
        "accuracy_mismatch": acc_mm,
        "rollback_mismatch": int(np.sum(prog["rollbacks"]
                                        != ref_out["rollbacks"])),
        "ingress_mismatch": int(prog["ingress_mismatch"]
                                + np.sum(prog["steps"] != ref_out["steps"])),
    }


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             cell=None, platform: str = "tpu", control: bool = False) -> dict:
    """Set-up, window, check. Returns the result line (a dict) with the
    checks under ``checks`` and, with ``control``, the control's readings
    under ``control``."""
    w, conf, traffic = cell if cell is not None else setup.cell(workload)
    if traffic["loop"] != "closed":
        raise SystemExit(f"bench: unknown loop {traffic['loop']!r}")
    chips = w["chips"]
    if chips != 1:
        raise SystemExit(f"bench: {workload} asks for {chips} chips; the "
                         f"harness has no mesh path yet")
    Reference = setup.reference(conf)
    devs = require_devices(chips, platform)
    import jax
    import jax.numpy as jnp

    enable_cache()
    t_set = {"start": time.perf_counter()}
    data = setup.make_data(conf, seed)
    t_set["data"] = time.perf_counter()
    # the pre-trained bank stands for one a deployment would load: the
    # reference trains it, and its seconds stay out of ``setup_s``
    ref = Reference(conf)
    bx, by = data["base"]
    with jax.default_device(devs[0]):
        base = jax.block_until_ready(ref.base_bank(
            bx, by, jax.random.PRNGKey(setup.seed32(seed, 2)),
            conf["base_epochs"], conf["s_offline"], conf["T"]))
    t_set["base"] = time.perf_counter()
    svc_seed = setup.seed32(seed, 1)
    svc = setup.build_service(conf, base, data["eval"], svc_seed)
    K = svc.n_replicas
    t_set["service"] = time.perf_counter()
    spans = loops.Spans(annotate=trace)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = loops.Tracer(tdir, traffic.get("trace_seconds"))
    opened: dict = {}

    def on_open():
        t = time.perf_counter()
        base_s = t_set["base"] - t_set["data"]
        opened["setup_s"] = t - T_PROCESS - base_s
        log(f"set-up {opened['setup_s']:.6f} s: imports and device "
            f"{t_set['start'] - T_PROCESS:.6f} s, data "
            f"{t_set['data'] - t_set['start']:.6f} s, service "
            f"{t_set['service'] - t_set['base']:.6f} s, warm-up "
            f"{t - t_set['service']:.6f} s; the base bank's "
            f"{base_s:.6f} s by the reference are left out")
        tracer.open()

    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xCE]))
    check = np.sort(rng.choice(K, min(conf["check_tenants"], K),
                               replace=False))
    loop = loops.ClosedLoop(svc, traffic, data["pool"], seed, check)
    try:
        rec = loop.run(spans, seconds, on_open, tracer)
    finally:
        tracer.close()
    t_open, t_close = rec["t_open"], rec["t_close"]
    window_s = t_close - t_open

    # what the program produced, read once the window has closed
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:chips])
    steps = np.asarray(svc.steps, np.int64)
    buffered = np.asarray(svc.buffered, np.int64)
    tk = loop.ticks
    sched_steps = np.sum(np.asarray(tk.cols), axis=0) if tk.cols else 0
    prog = {
        "banks": np.asarray(svc.ss.tm.ta_state[jnp.asarray(check)]),
        "acc": np.stack(tk.acc) if tk.acc else np.zeros((0, len(check))),
        "rollbacks": np.asarray(svc.rollbacks)[check],
        "steps": steps[check],
        "ingress_mismatch": int(np.sum(steps + buffered != rec["accepted"])
                                + np.sum(steps[check] != sched_steps)),
    }
    del svc
    loop.svc = None
    gc.collect()

    # the reference, for the checked tenants only
    rows = [loop.rows_of(int(r), int(n)) for r, n in
            zip(check, np.atleast_1d(sched_steps))]
    keys = tenant_keys(svc_seed, check)
    ev = data["eval"]
    t_ref = time.perf_counter()
    ref_out = ref.replay(np.asarray(base), keys, [r[0] for r in rows],
                         [r[1] for r in rows], tk.schedule(), ev[0], ev[1],
                         conf["s_online"], conf["T"])
    checks = compare(ref_out, prog)
    log(f"reference replay of {len(check)} tenants over {len(tk.start)} "
        f"ticks took {time.perf_counter() - t_ref:.3f} s")

    for name in sorted(spans.by_name):
        sp = spans.within(name, t_open, t_close)
        log(f"host spans in the window: {name} x{len(sp)} "
            f"{sum(b - a for a, b in sp):.6f} s")
    out: dict = {}
    if control:
        out["control"] = control_readings(Reference, conf, ref_out, base,
                                          keys, rows, tk, ev)

    # end-to-end metrics
    e2e = {"setup_s": opened["setup_s"],
           "trained_rows_per_s": rec["rows"] / window_s}
    log(f"window {window_s:.6f} s, {rec['ticks']} ticks, "
        f"{rec['rows']} rows trained, "
        f"{sum(tk.analysed[rec['first_tick']:])} analyses")

    bm = setup.benchmark()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    if not trace:
        metrics = {}
        for m in bm["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench.trace import Reduction

        red = Reduction.from_file(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            conf=conf, traffic=traffic, chips=chips, trace=red,
            peak=_peaks(devs[0].device_kind), work=work,
            t_open=t_open, t_close=t_close,
            spans={k: spans.within(k, t_open, t_close)
                   for k in spans.by_name},
            counts=_counts(rec, tk, tracer.t0, tracer.t0 + red.window_s))
        metrics = per_layer(bm, workload, ctx)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_gaps(10)}
    correct = all(v <= 0 for v in checks.values())
    result = {"correct": correct, "attempted": int(rec["rows"]),
              "failed": 0, "metrics": metrics, "device": device}
    if "breakdown" in out:
        result["breakdown"] = out["breakdown"]
    if "control" in out:
        result["control"] = out["control"]
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return result


def _peaks(kind: str):
    from bench.peaks import peaks

    return peaks(kind)


def _counts(rec, tk, t0, t1) -> dict:
    """Work done in the window (host spans) and inside the traced part of
    it (``traced_*``: what device metrics divide by the traced seconds)."""
    first = rec["first_tick"]
    inside = [i for i in range(first, len(tk.start))
              if tk.start[i] >= t0 and tk.end[i] <= t1]
    return {"ticks": len(tk.start) - first,
            "rows_trained": sum(tk.total[first:]),
            "rows_accepted": rec["rows_accepted"],
            "traced_rows_trained": sum(tk.total[i] for i in inside),
            "traced_tenant_ticks": sum(tk.tenants[i] for i in inside)}


def per_layer(bm: dict, workload: str, ctx) -> dict:
    """Every per-layer metric of this cell, read by its own reader; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in bm["per_layer"]:
        if workload not in m["workloads"]:
            continue
        path = os.path.join(HERE, "metrics", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def control_readings(Reference, conf, ref_out, base, keys, rows, tk,
                     ev) -> dict:
    """The control: the configuration's reference in bfloat16 put in the
    program's place, compared with the float32 reference as the program
    is."""
    import jax.numpy as jnp

    low = Reference(conf, u_dtype=jnp.bfloat16)
    ctl = low.replay(np.asarray(base), keys, [r[0] for r in rows],
                     [r[1] for r in rows], tk.schedule(), ev[0], ev[1],
                     conf["s_online"], conf["T"])
    return compare(ref_out, {"banks": ctl["banks"], "acc": ctl["acc"],
                             "rollbacks": ctl["rollbacks"],
                             "steps": ctl["steps"], "ingress_mismatch": 0})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        log(f"the program is not in this checkout (src/repro): {e}")
        return 2
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 3
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
