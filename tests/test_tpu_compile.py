"""Compiles for a described TPU v5e chip that is not attached.

The Pallas kernels of the main path, one machine's calls of them (the
R = 1 grids) and the packed fleet drain compile at the f=784 preset (``configs/tm_mnist.py``: 10 classes x 64 clauses x 1568
literals) with interpret mode off, for one chip and for a 2x2 mesh; the
patch entries at the Convolutional TM's widths (361 patches of 272
literals, 10 x 2,000 clauses). The
compiler refuses here what a chip would refuse: unaligned tiles, too much
fast memory, a program that does not fit HBM, a kernel GSPMD cannot
partition. Nothing runs, so nothing here is a result or a time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under pytest-xdist only the
worker given this file does.
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import tm_mnist
from repro.core import feedback as fb_mod
from repro.core import init_runtime
from repro.core import online as online_mod
from repro.core import tm as tm_mod
from repro.core.online import SessionState
from repro.core.tm import TMConfig, TMState
from repro.data import buffer as buf_mod
from repro.distributed import sharding as shard_mod
from repro.kernels import dispatch, packing

CFG = tm_mnist.CONFIG.tm
C, J, L = CFG.max_classes, CFG.max_clauses, CFG.n_literals
# the patch entries at the Convolutional TM's widths (bench/configs/
# ctm-digits28-w10.json): 28x28 images, 10x10 window, 361 patches
CTM = TMConfig(n_features=784, max_classes=10, max_clauses=2000, n_states=128,
               image=(28, 28), window=(10, 10))
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
    if "patch" in name:
        bank = (R, CTM.max_classes, CTM.max_clauses, CTM.n_literals)
        if name.startswith("feedback"):
            return ([(bank, CTM.state_dtype), (bank, jnp.bool_)]
                    + [(bank[:3], jnp.bool_)] * 3
                    + [(bank, jnp.float32), ((R,), jnp.float32)],
                    dict(n_states=CTM.n_states, s_policy=CTM.s_policy,
                         boost_true_positive=CTM.boost_true_positive))
        return ([(bank, jnp.bool_),
                 ((R, 1, CTM.n_patches, CTM.n_literals), jnp.bool_),
                 ((R, 1) + bank[1:3], jnp.float32)], dict(training=True))
    packed = name.endswith("packed")
    inc = (R, C, J) + ((W,) if packed else (L,))
    inc_dt = jnp.uint32 if packed else jnp.bool_
    if name.startswith("feedback"):
        ops = [((R, C, J, L), CFG.state_dtype), ((R, L), jnp.bool_),
               ((R, C, J), jnp.bool_), ((R, C, J), jnp.bool_),
               ((R, C, J), jnp.bool_), ((R, C, J, L), jnp.float32),
               ((R,), jnp.float32)]
        return ops, dict(n_states=CFG.n_states, s_policy=CFG.s_policy,
                         boost_true_positive=CFG.boost_true_positive)
    lit = (R,) if name == "clause_eval_replicated" else (R, B)
    lit += (W,) if packed else (L,)
    ops = [(inc, inc_dt)]
    if "pruned" in name:
        ops.append(((R, C, M), jnp.int32))
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


# One machine's core functions, each now the R = D = 1 call of a replicated
# entry: (function of (runtime, operands...), operand shapes+dtypes).
_F, _WF = CFG.n_features, packing.n_words(CFG.n_features)
_BANK = ((C, J, L), CFG.state_dtype)
_SEL = ((C, M), jnp.int32)
SINGLE_MACHINE_CALLS = {
    "eval_clauses": (
        lambda rt, inc, lit: tm_mod.eval_clauses(CFG, inc, lit, rt,
                                                 training=True),
        [((C, J, L), jnp.bool_), ((L,), jnp.bool_)]),
    "eval_clauses_batch": (
        lambda rt, inc, lit: tm_mod.eval_clauses_batch(CFG, inc, lit, rt,
                                                       training=False),
        [((C, J, L), jnp.bool_), ((B, L), jnp.bool_)]),
    "eval_clauses_batch_packed": (
        lambda rt, inc, lit: tm_mod.eval_clauses_batch_packed(
            CFG, inc, lit, rt, training=False),
        [((C, J, W), jnp.uint32), ((B, W), jnp.uint32)]),
    "forward_batch_pruned": (
        lambda rt, ta, xs, sel: tm_mod.forward_batch_pruned(
            CFG, TMState(ta_state=ta), rt, xs, sel)[1],
        [_BANK, ((B, _F), jnp.bool_), _SEL]),
    "forward_batch_pruned_packed": (
        lambda rt, ta, xs, sel: tm_mod.forward_batch_pruned(
            CFG, TMState(ta_state=ta), rt, xs, sel)[1],
        [_BANK, ((B, _WF), jnp.uint32), _SEL]),
    "train_update": (
        lambda rt, ta, x, y, key: fb_mod.train_update(
            CFG, TMState(ta_state=ta), rt, x, y, key)[0].ta_state,
        [_BANK, ((_F,), jnp.bool_), ((), jnp.int32), ((2,), jnp.uint32)]),
}


@pytest.mark.parametrize("name", SINGLE_MACHINE_CALLS)
def test_single_machine_call_compiles_at_f784(topo, pallas, name):
    """A single machine runs the replicated kernel grids at R = D = 1, and
    every single-machine caller relies on that grid compiling for the
    chip: clause eval, batch, batch packed, both pruned entries (through
    ``forward_batch_pruned``) and feedback (through ``train_update``)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    fn, ops = SINGLE_MACHINE_CALLS[name]
    rt = jax.eval_shape(lambda: init_runtime(CFG, s=1.5, T=32))
    rt = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), rt)
    args = [_sds(s, d, one_chip) for s, d in ops]
    compiled = jax.jit(fn).lower(rt, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _drain_args(sharding_of, cfg=CFG, k=K):
    """Shapes of the packed drain's operands at ``k`` tenants of ``cfg``,
    placed by ``sharding_of(tree) -> tree of shardings``."""
    buf = jax.eval_shape(
        lambda: buf_mod.make(CAPACITY, cfg.n_features, packed=True))
    ss = SessionState(
        tm=TMState(ta_state=jax.ShapeDtypeStruct(
            (k, cfg.max_classes, cfg.max_clauses, cfg.n_literals),
            cfg.state_dtype)),
        buf=jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((k,) + a.shape, a.dtype), buf),
        step=jax.ShapeDtypeStruct((k,), jnp.int32),
    )
    rt = jax.eval_shape(lambda: init_runtime(cfg, s=1.5, T=32))
    limit = jax.ShapeDtypeStruct((k,), jnp.int32)
    keys = jax.ShapeDtypeStruct((k, 2), jnp.uint32)
    tree = (ss, rt, limit, keys)
    return jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s), tree,
                        sharding_of(tree))


def _compile_drain(args, cfg=CFG):
    ss, rt, limit, keys = args
    return online_mod._consume_many_replicated.lower(
        cfg, CHUNK, ss, rt, limit, keys, monitor=False).compile()


_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")


def _while_body_ops(hlo: str) -> list[str]:
    """Instruction lines of every computation a ``while`` body reaches
    (fusions and calls included) in an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = _HEADER.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    todo = [b for lines in comps.values() for line in lines
            if " while(" in line
            for b in re.findall(r"body=%?([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += [t for t in re.findall(r"%([\w.\-]+)", line) if t in comps]
    return [line for c in seen for line in comps[c]]


# the iris machine of bench/configs/tm-iris-paper-k4096.json
IRIS = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=16)


@pytest.mark.parametrize("cfg", [IRIS, CFG], ids=["iris", "f784"])
def test_drain_step_builds_kernel_control_planes_without_scatters(
        topo, pallas, cfg):
    """The scan step's control planes (feedback ``ctl`` and ``p``, clause
    count ``rhs``) are dense writes: no dynamic-update-slice or scatter in
    the drain's loop comes from the two kernels' operand preparation."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compile_drain(_drain_args(
        lambda t: jax.tree.map(lambda _: one_chip, t), cfg=cfg, k=64), cfg=cfg)
    body = _while_body_ops(compiled.as_text())
    kernels = ("feedback_plane_replicated", "clause_counts_replicated")
    assert any("custom-call(" in op and any(k in op for k in kernels)
               for op in body)
    bad = [op.strip()[:160] for op in body
           if re.search(r" (dynamic-update-slice|scatter)\(", op)
           and any(f"jit({k})" in op for k in kernels)]
    assert not bad, bad


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
