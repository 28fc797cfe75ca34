import os
# A CPU-only tool: it forces virtual host devices and re-launches itself per
# cell, so it must never take a TPU (a chip belongs to one process).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: parameters,
optimizer state, inputs and caches are ShapeDtypeStructs (zero allocation);
`jit(...).lower().compile()` runs the full GSPMD partitioning pipeline for the
production meshes:

    single-pod: (data=16, model=16)            = 256 chips
    multi-pod:  (pod=2, data=16, model=16)     = 512 chips

Artifacts per cell (memory analysis, cost analysis, collective stats, HLO
text) are dumped under artifacts/dryrun/ for the roofline model
(repro.roofline.model) and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch gemma3-1b --shape decode_32k --mesh multi
  python -m repro.launch.dryrun --all [--mesh both] [--subprocess]
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_mod
from repro.models import params as P
from repro.models import stubs, transformer
from repro.roofline import hlo_parse
from repro.train import optimizer as opt_mod
from repro.train import train_step as ts_mod

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "../../../artifacts/dryrun")


def _policy_for(cfg: ModelConfig, shape: ShapeConfig) -> shd.ShardingPolicy:
    if shape.kind == "train":
        return shd.ShardingPolicy()
    # Serving is weight-stationary: NO FSDP (a per-step weight all-gather
    # would dominate decode), experts sharded over `data` (EP all-to-all),
    # expert_ff/vocab/heads TP over `model`. SP shards very long sequences.
    rules = dict(shd.DEFAULT_RULES)
    rules["experts"] = "data"
    seq_axis = "model" if (shape.kind == "decode"
                           and shape.global_batch < 16) else None
    return shd.ShardingPolicy(rules=rules, fsdp=False, seq_axis=seq_axis)


def _moe_groups(cfg: ModelConfig, mesh) -> int:
    if cfg.moe is None:
        return 1
    g = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            g *= mesh.shape[ax]
    return g


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Returns (jitted_fn, example_args_abstract) for one cell."""
    policy = _policy_for(cfg, shape)
    specs = transformer.model_specs(cfg)
    # Serving cells deploy bf16 weights (standard practice, and half the
    # weight-gather traffic); training keeps fp32 masters + bf16 compute.
    p_dtype = (jnp.dtype(cfg.param_dtype) if shape.kind == "train"
               else jnp.dtype(cfg.compute_dtype))
    params_abs = P.abstract(specs, p_dtype)
    p_shard = shd.param_shardings(specs, mesh, policy)
    batch_abs = stubs.input_specs(cfg, shape)

    if shape.kind == "train":
        tc = ts_mod.TrainConfig(
            opt=opt_mod.OptConfig(moment_dtype=cfg.adam_dtype),
            microbatches=cfg.train_microbatches,
            moe_num_groups=_moe_groups(cfg, mesh),
        )
        mu_abs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(cfg.adam_dtype)),
            params_abs)
        state_abs = ts_mod.TrainState(
            params=params_abs,
            opt=opt_mod.OptState(
                step=jax.ShapeDtypeStruct((), jnp.int32),
                mu=mu_abs, nu=mu_abs),
            compress=None,
        )
        state_shard = ts_mod.TrainState(
            params=p_shard,
            opt=opt_mod.OptState(
                step=shd.NamedSharding(mesh, shd.PS()),
                mu=p_shard, nu=p_shard),
            compress=None,
        )
        b_shard = shd.batch_shardings(batch_abs, mesh, policy)

        def step(state, batch):
            new_state, metrics = ts_mod.train_step(cfg, tc, state, batch)
            # Pin the output placement: the updated params/moments stay FSDP-
            # sharded (otherwise GSPMD may replicate them through the update,
            # turning the gradient reduce-scatter into a full all-reduce).
            new_state = jax.lax.with_sharding_constraint(new_state, state_shard)
            return new_state, metrics

        fn = jax.jit(step, in_shardings=(state_shard, b_shard),
                     donate_argnums=(0,))
        return fn, (state_abs, batch_abs)

    if shape.kind == "prefill":
        b_shard = shd.batch_shardings(batch_abs, mesh, policy)
        pc_shard = shd.cache_shardings(
            transformer.cache_struct(cfg, shape.global_batch, shape.seq_len),
            mesh, policy)

        def prefill_fn(params, batch):
            logits, cache = transformer.prefill(cfg, params, batch,
                                                shape.seq_len)
            cache = jax.lax.with_sharding_constraint(cache, pc_shard)
            return logits, cache

        fn = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard))
        return fn, (params_abs, batch_abs)

    # decode
    cache_abs = batch_abs.pop("cache")
    c_shard = shd.cache_shardings(cache_abs, mesh, policy)
    b_shard = shd.batch_shardings(batch_abs, mesh, policy)

    def serve_step(params, batch, cache):
        logits, new_cache = transformer.decode_step(cfg, params, batch, cache)
        new_cache = jax.lax.with_sharding_constraint(new_cache, c_shard)
        return logits, new_cache

    fn = jax.jit(serve_step, in_shardings=(p_shard, b_shard, c_shard),
                 donate_argnums=(2,))
    return fn, (params_abs, batch_abs, cache_abs)


def build_tm_cell(mesh):
    """The paper's technique on the production mesh: the (s x T x orderings)
    cross-validation/HP-search grid as ONE program, replicas sharded over
    every mesh axis (goal (ii) at pod scale). 8 x 4 x 128 = 4096 TM replicas
    train 10 epochs on 30-row offline sets and report validation accuracy."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.configs.tm_iris import CONFIG as TM_SYS
    from repro.core import hpsearch

    cfg = TM_SYS.tm
    O, n_off, n_val, f = 128, 30, 60, cfg.n_features
    s_grid = jax.ShapeDtypeStruct((16,), jnp.float32)
    T_grid = jax.ShapeDtypeStruct((4,), jnp.int32)
    repl = NamedSharding(mesh, PS())
    # s-grid over `data`, orderings over `model`: 16 x 4 x 128 = 8192 TM
    # replicas, 32/device at 256 chips (pod axis replicates when present).
    osh = NamedSharding(mesh, PS("model"))
    gsh = NamedSharding(mesh, PS("data"))

    off = (jax.ShapeDtypeStruct((O, n_off, f), jnp.bool_),
           jax.ShapeDtypeStruct((O, n_off), jnp.int32))
    val = (jax.ShapeDtypeStruct((O, n_val, f), jnp.bool_),
           jax.ShapeDtypeStruct((O, n_val), jnp.int32))
    keys = jax.ShapeDtypeStruct((O, 2), jnp.uint32)

    def grid_fn(s_grid, T_grid, off, val, keys):
        return hpsearch.grid_search_device(cfg, s_grid, T_grid, off, val,
                                           keys, 10)

    fn = jax.jit(
        grid_fn,
        in_shardings=(gsh, repl,
                      (osh, osh), (osh, osh), osh),
        out_shardings=NamedSharding(mesh, PS("data", None, "model")),
    )
    return fn, (s_grid, T_grid, off, val, keys)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             save_hlo: bool = True) -> dict:
    if arch in ("tm-iris", "tm_iris"):
        return run_tm_cell(mesh_kind, out_dir, save_hlo)
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "skip", "reason": None,
    }
    if shape_name == "long_500k" and not cfg.supports_long_context:
        result["reason"] = "pure full-attention arch (DESIGN.md skip table)"
        return result

    if mesh_kind.startswith("multi"):
        n_pods = int(mesh_kind[5:]) if len(mesh_kind) > 5 else 2
        mesh = mesh_mod.make_production_mesh(multi_pod=True, n_pods=n_pods)
    else:
        mesh = mesh_mod.make_production_mesh(multi_pod=False)
    n_dev = mesh.devices.size
    t0 = time.time()
    from repro.distributed import autoshard
    expert_axis = "model" if shape.kind == "train" else "data"
    with mesh, autoshard.use(mesh, moe_expert_axis=expert_axis):
        fn, args = build_cell(cfg, shape, mesh)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_d = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes")
        if hasattr(mem, k)
    }
    cost = compiled.cost_analysis() or {}
    cost_d = {k: float(v) for k, v in cost.items()
              if isinstance(v, (int, float))}
    hlo = compiled.as_text()
    coll = hlo_parse.parse_collectives(hlo, n_dev)

    result.update({
        "status": "ok",
        "n_devices": n_dev,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_d,
        "cost": cost_d,
        "collectives": {
            "bytes_by_op": coll.bytes_by_op,
            "count_by_op": coll.count_by_op,
            "wire_bytes_by_op": coll.wire_bytes_by_op,
            "total_wire_bytes": coll.total_wire_bytes,
        },
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    })
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch.replace('.', '_')}__{shape_name}__{mesh_kind}"
    if save_hlo:
        with open(os.path.join(out_dir, stem + ".hlo.txt"), "w") as f:
            f.write(hlo)
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def run_tm_cell(mesh_kind: str, out_dir: str, save_hlo: bool = True) -> dict:
    """Lower + compile the TM hp-search grid on the production mesh."""
    if mesh_kind.startswith("multi"):
        mesh = mesh_mod.make_production_mesh(multi_pod=True)
    else:
        mesh = mesh_mod.make_production_mesh(multi_pod=False)
    t0 = time.time()
    with mesh:
        fn, args = build_tm_cell(mesh)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = hlo_parse.parse_collectives(hlo, mesh.devices.size)
    result = {
        "arch": "tm-iris", "shape": "hpsearch_grid", "mesh": mesh_kind,
        "status": "ok", "n_devices": int(mesh.devices.size),
        "compile_s": round(time.time() - t0, 2),
        "lower_s": 0.0,
        "replicas": 16 * 4 * 128,
        "memory": {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes")
            if hasattr(mem, k)
        },
        "cost": {k: float(v)
                 for k, v in (compiled.cost_analysis() or {}).items()
                 if isinstance(v, (int, float))},
        "collectives": {
            "count_by_op": coll.count_by_op,
            "total_wire_bytes": coll.total_wire_bytes,
        },
        "param_count": 0, "active_param_count": 0,
    }
    os.makedirs(out_dir, exist_ok=True)
    stem = f"tm-iris__hpsearch_grid__{mesh_kind}"
    if save_hlo:
        with open(os.path.join(out_dir, stem + ".hlo.txt"), "w") as f:
            f.write(hlo)
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def all_cells(mesh_kinds):
    for arch in configs.ARCH_IDS:
        cfg = configs.get_config(arch)
        for shape_name in SHAPES:
            if shape_name == "long_500k" and not cfg.supports_long_context:
                continue
            for mk in mesh_kinds:
                yield configs.get_config(arch).arch_id, shape_name, mk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "multi4", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each cell in a fresh process (bounded memory)")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--no-hlo", action="store_true")
    args = ap.parse_args()

    mesh_kinds = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    out = os.path.abspath(args.out)

    if args.all:
        cells = list(all_cells(mesh_kinds))
        failures = 0
        for i, (arch, shape_name, mk) in enumerate(cells):
            stem = f"{arch.replace('.', '_')}__{shape_name}__{mk}"
            if os.path.exists(os.path.join(out, stem + ".json")):
                print(f"[{i+1}/{len(cells)}] {stem}: cached")
                continue
            if args.subprocess:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name, "--mesh", mk,
                       "--out", out] + (["--no-hlo"] if args.no_hlo else [])
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True)
                ok = r.returncode == 0
                print(f"[{i+1}/{len(cells)}] {stem}: "
                      f"{'ok' if ok else 'FAIL'} ({time.time()-t0:.0f}s)")
                if not ok:
                    failures += 1
                    print(r.stdout[-2000:])
                    print(r.stderr[-2000:])
            else:
                try:
                    res = run_cell(arch, shape_name, mk, out,
                                   save_hlo=not args.no_hlo)
                    print(f"[{i+1}/{len(cells)}] {stem}: {res['status']}")
                except Exception:
                    failures += 1
                    traceback.print_exc()
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape
    for mk in mesh_kinds:
        res = run_cell(args.arch, args.shape, mk, out,
                       save_hlo=not args.no_hlo)
        print(json.dumps({k: v for k, v in res.items()
                          if k not in ("collectives",)}, indent=1))
        if res["status"] == "ok":
            print("collective wire bytes:",
                  res["collectives"]["total_wire_bytes"])


if __name__ == "__main__":
    main()
