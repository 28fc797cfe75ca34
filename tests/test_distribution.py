"""Sharding rules, autoshard hints, HLO cost model, small-mesh pjit run."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.distributed import autoshard
from repro.distributed import sharding as shd
from repro.models.params import PSpec
from repro.roofline import hlo_cost


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_spec_partition_rules():
    mesh = _mesh()
    pol = shd.ShardingPolicy(fsdp=False)
    ps = shd.spec_partition(PSpec((100, 64), ("vocab", "embed")), mesh, pol)
    assert ps == PS("model", None)  # vocab -> model (divisible by 1)
    # non-divisible falls back to replication, never crashes
    mesh16 = jax.make_mesh((1,), ("model",))
    pol = shd.ShardingPolicy(fsdp=False)
    ps = shd.spec_partition(PSpec((7, 3), ("kv_heads", "head_dim")), mesh16, pol)
    assert ps == PS("model", None) or ps == PS(None, None)


def test_fsdp_shards_largest_free_dim():
    # AbstractMesh: rule evaluation needs only mesh.shape, not real devices
    mesh = jax.sharding.AbstractMesh((2, 16), ("data", "model"))
    pol = shd.ShardingPolicy()
    ps = shd.spec_partition(PSpec((128, 64), ("embed", "ff")), mesh, pol)
    assert ps == PS("data", "model")  # ff -> TP; fsdp picks embed over data


def test_spec_partition_nondivisible_replicates():
    mesh = jax.sharding.AbstractMesh((16,), ("model",))
    pol = shd.ShardingPolicy(fsdp=False)
    ps = shd.spec_partition(PSpec((7, 3), ("kv_heads", "head_dim")), mesh, pol)
    assert ps == PS(None, None)


def test_autoshard_hint_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = autoshard.hint(x, "data", None)
    assert y is x


def test_autoshard_settings():
    mesh = jax.make_mesh((1,), ("data",))
    assert autoshard.setting("moe_expert_axis", "model") == "model"
    with autoshard.use(mesh, moe_expert_axis="data"):
        assert autoshard.setting("moe_expert_axis", "model") == "data"


SYNTH_HLO = textwrap.dedent("""\
    HloModule test

    %cond (p: (s32[], f32[8,8])) -> pred[] {
      %p = (s32[], f32[8,8]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %c = s32[] constant(5)
      ROOT %lt = pred[] compare(%i, %c), direction=LT
    }

    %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
      %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={{0,1,2,3}}, to_apply=%sum
      %one = s32[] constant(1)
      %ni = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,8]) tuple(%ni, %ar)
    }

    ENTRY %main (a: f32[8,8]) -> f32[8,8] {
      %a = f32[8,8]{1,0} parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[8,8]) tuple(%zero, %a)
      %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
      ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
    }
""")


def test_hlo_cost_trip_count_multiplies():
    c = hlo_cost.analyze(SYNTH_HLO, n_devices=4)
    # dot: 2*8*8*8 = 1024 flops x 5 iterations
    assert abs(c.dot_flops - 5 * 1024) < 1e-6
    # all-reduce: 8*8*4 bytes, group 4 -> wire 2*(3/4)*256 = 384 x 5
    assert abs(c.wire_bytes_by_op["all-reduce"] - 5 * 384) < 1e-6


def test_hlo_cost_known_trip_count_annotation():
    hlo = SYNTH_HLO.replace(
        'condition=%cond, body=%body',
        'condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}')
    c = hlo_cost.analyze(hlo, n_devices=4)
    assert abs(c.dot_flops - 7 * 1024) < 1e-6


SMALL_MESH_SCRIPT = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.configs.base import ShapeConfig
    from repro.distributed import autoshard, sharding as shd
    from repro.models import params as P, stubs, transformer

    cfg = configs.get_smoke_config("granite_8b")
    # the model's sharding is GSPMD's: Auto axes (make_mesh defaults to
    # Explicit, under which the batch-sharded embedding gather from the
    # FSDP-sharded table names the data axis twice)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    policy = shd.ShardingPolicy()
    specs = transformer.model_specs(cfg)
    prm = P.materialize(specs, jax.random.PRNGKey(0), jnp.float32)
    p_shard = shd.param_shardings(specs, mesh, policy)
    prm_sharded = jax.tree.map(jax.device_put, prm, p_shard)
    batch = stubs.synthetic_batch(cfg, ShapeConfig("t", 32, 4, "train"))
    b_shard = shd.batch_shardings(batch, mesh, policy)
    batch = jax.tree.map(jax.device_put, batch, b_shard)

    with mesh, autoshard.use(mesh):
        loss_sharded, _ = jax.jit(
            lambda p, b: transformer.loss_fn(cfg, p, b))(prm_sharded, batch)
    loss_local, _ = transformer.loss_fn(cfg, prm, jax.device_get(batch) | {})
    err = abs(float(loss_sharded) - float(loss_local))
    assert err < 1e-3, (float(loss_sharded), float(loss_local))

    # autoshard hint: divisible dim gets sharded, non-divisible replicates
    from jax.sharding import PartitionSpec as PS
    with autoshard.use(mesh):
        y = autoshard.hint(jnp.ones((8, 4)), "data", None)
        assert y.sharding.spec == PS("data", None), y.sharding
        y2 = autoshard.hint(jnp.ones((3, 4)), "data", None)  # 3 % 4 != 0
    print("OK", float(loss_sharded))
""")


def test_pjit_small_mesh_matches_single_device():
    """8-device SPMD loss == single-device loss (subprocess: own XLA_FLAGS)."""
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SMALL_MESH_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
