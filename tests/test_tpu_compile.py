"""Compiles for a described TPU v5e chip that is not attached.

The Pallas kernels of the main path and the packed fleet drain compile at
the f=784 preset (``configs/tm_mnist.py``: 10 classes x 64 clauses x 1568
literals) with interpret mode off, for one chip and for a 2x2 mesh. The
compiler refuses here what a chip would refuse: unaligned tiles, too much
fast memory, a program that does not fit HBM, a kernel GSPMD cannot
partition. Nothing runs, so nothing here is a result or a time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist only the
worker given this file does.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import tm_mnist
from repro.core import init_runtime
from repro.core import online as online_mod
from repro.core.online import SessionState
from repro.core.tm import TMState
from repro.data import buffer as buf_mod
from repro.distributed import sharding as shard_mod
from repro.kernels import dispatch, packing

CFG = tm_mnist.CONFIG.tm
C, J, L = CFG.max_classes, CFG.max_clauses, CFG.n_literals
W = 2 * packing.n_words(CFG.n_features)   # packed include/literal words
B, R, M = 64, 8, 32                        # batch, replicas (D = R), budget
HBM_BYTES = 16 * 10**9                     # one v5e chip
K, CHUNK, CAPACITY = 256, 16, 64           # the drain at fleet scale


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described compile is written to the persistent cache but cannot
        # be read back without a chip: keep the cache out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def pallas(topo):
    """``auto`` as it resolves on a TPU: the compiled (not interpreted)
    Pallas backend. The process still runs on the CPU, so the platform
    query is steered here, and the backend cache is fresh for the module."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        mp.setattr(dispatch, "_CACHE", {})
        kb = dispatch.resolve("auto")
        assert kb.name == "pallas" and not kb.interpret
        yield kb


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _entry_operands(name: str):
    """(positional operand shapes+dtypes, static kwargs) of one entry."""
    rep = "replicated" in name
    packed = name.endswith("packed")
    inc = ((R,) if rep else ()) + (C, J) + ((W,) if packed else (L,))
    inc_dt = jnp.uint32 if packed else jnp.bool_
    if name.startswith("feedback"):
        lead = (R,) if rep else ()
        ops = [(lead + (C, J, L), CFG.state_dtype), (lead + (L,), jnp.bool_),
               (lead + (C, J), jnp.bool_), (lead + (C, J), jnp.bool_),
               (lead + (C, J), jnp.bool_), (lead + (C, J, L), jnp.float32),
               (lead, jnp.float32)]
        return ops, dict(n_states=CFG.n_states, s_policy=CFG.s_policy,
                         boost_true_positive=CFG.boost_true_positive)
    lit = ((R,) if rep else ()) + (() if name == "clause_eval_replicated"
                                   else (B,) if name != "clause_eval" else ())
    lit += (W,) if packed else (L,)
    ops = [(inc, inc_dt)]
    if "pruned" in name:
        ops.append((((R,) if rep else ()) + (C, M), jnp.int32))
    ops.append((lit, inc_dt))
    return ops, dict(training=False)


@pytest.mark.parametrize("name", dispatch.ENTRIES)
def test_kernel_entry_compiles_at_f784(topo, pallas, name):
    one_chip = SingleDeviceSharding(topo.devices[0])
    ops, kw = _entry_operands(name)
    fn = getattr(pallas, name)
    if name.startswith("feedback"):
        f = (lambda *a: fn(*a[:-1], s=a[-1], **kw))
    else:
        f = partial(fn, **kw)
    args = [_sds(s, d, one_chip) for s, d in ops]
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _drain_args(sharding_of):
    """Shapes of the packed drain's operands at K tenants, placed by
    ``sharding_of(tree) -> tree of shardings``."""
    buf = jax.eval_shape(
        lambda: buf_mod.make(CAPACITY, CFG.n_features, packed=True))
    ss = SessionState(
        tm=TMState(ta_state=jax.ShapeDtypeStruct((K, C, J, L),
                                                 CFG.state_dtype)),
        buf=jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype), buf),
        step=jax.ShapeDtypeStruct((K,), jnp.int32),
    )
    rt = jax.eval_shape(lambda: init_runtime(CFG, s=1.5, T=32))
    limit = jax.ShapeDtypeStruct((K,), jnp.int32)
    keys = jax.ShapeDtypeStruct((K, 2), jnp.uint32)
    tree = (ss, rt, limit, keys)
    return jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s), tree,
                        sharding_of(tree))


def _compile_drain(args):
    ss, rt, limit, keys = args
    return online_mod._consume_many_replicated.lower(
        CFG, CHUNK, ss, rt, limit, keys, monitor=False).compile()


def test_packed_drain_k256_compiles_and_fits_one_chip(topo, pallas):
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compile_drain(_drain_args(
        lambda t: jax.tree.map(lambda _: one_chip, t)))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used


def test_packed_drain_k256_compiles_on_four_chips(topo, pallas):
    """The replica axis sharded over a 2x2 mesh: Mosaic kernels cannot be
    partitioned by GSPMD, so the kernels must run under shard_map."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    args = _drain_args(
        lambda t: shard_mod.replica_shardings(t, mesh, n_replicas=K))
    with jax.set_mesh(mesh):
        compiled = _compile_drain(args)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
