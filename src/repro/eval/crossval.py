"""Replica-parallel cross-validation engine (paper §3.6.1, §5 — goal ii).

The paper's "inbuilt cross-validation infrastructure" re-runs every
experiment over block orderings and sweeps (s, T) "within a short period of
time". Here the whole sweep — orderings x s-grid x T-grid — is compiled as
ONE program over a leading *replica* axis:

* replica layout is grid-major / ordering-minor: ``r = (si*G + ti)*O + o``,
  so the ``D = O`` data streams (block orderings) are the fastest-varying
  factor and every per-data operand (datapoints, labels, RNG streams) is
  stored once per ordering and broadcast across the (s, T) grid — the
  kernel contract's ``r % D`` rule (kernels/dispatch.py);
* training runs through :func:`repro.core.feedback.train_epochs_replicated`
  — one ``lax.scan`` over datapoints whose body advances all R TA banks in
  a single fused ``feedback_step_replicated`` plane;
* analysis is one ``clause_eval_batch_replicated`` contraction for all
  replicas;
* the replica axis is shardable over a device mesh via
  :func:`repro.distributed.sharding.replica_shardings` (pass ``mesh=``).

Results are **bit-identical** to looping ``hpsearch._one_cell`` over cells
(asserted in tests/test_crossval.py and benchmarks/crossval.py) while the
sweep wall-clock is >= 2x faster than the pre-replica vmap-of-scan path on
CPU (tracked in BENCH_crossval.json).

The same replica machinery also runs the paper's Fig-3 system flow
(offline -> online cycles -> per-cycle analysis) for all orderings at once:
:meth:`CrossValRun.system` is what ``manager.run_orderings`` and the
figure benchmarks are thin callers of.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import accuracy as acc_mod
from repro.core import feedback as fb_mod
from repro.core import manager as mgr
from repro.core import tm as tm_mod
from repro.core.tm import TMConfig, TMRuntime, TMState
from repro.distributed import sharding as shard_mod


class SweepResult(NamedTuple):
    """One (s x T x orderings) sweep's output (mirrors hpsearch.GridResult)."""

    s_grid: np.ndarray        # [S]
    T_grid: np.ndarray        # [G]
    val_accuracy: jax.Array   # [S, G, O] per-ordering validation accuracy
    mean_accuracy: jax.Array  # [S, G]
    replicas: int             # R = S * G * O
    wall_s: float             # device wall-clock of the compiled sweep
    replicas_per_s: float


class SystemResult(NamedTuple):
    """All-orderings Fig-3 system run (mirrors manager.run_system outputs)."""

    state: TMState            # leaves [O, ...]
    accuracies: jax.Array     # [O, 1 + n_cycles, 3] (offline/validation/online)
    activity: jax.Array       # [O, n_cycles]
    replicas: int
    wall_s: float


def replicate_state(cfg: TMConfig, n_replicas: int) -> TMState:
    """R copies of the deterministic boundary init (init_state without key)."""
    base = tm_mod.init_state(cfg).ta_state
    return TMState(
        ta_state=jnp.broadcast_to(base, (n_replicas,) + base.shape)
    )


def grid_layout(
    s_values, T_values, n_orderings: int
) -> tuple[jax.Array, jax.Array]:
    """Per-replica (s, T) for the grid-major / ordering-minor layout.

    ``r = (si*G + ti)*O + o`` — the ordering axis is fastest-varying so data
    streams are shared across the grid via the kernels' ``r % D`` rule.
    """
    s_grid = jnp.asarray(s_values, dtype=jnp.float32)
    T_grid = jnp.asarray(T_values, dtype=jnp.int32)
    G, O = T_grid.shape[0], n_orderings
    s_rep = jnp.repeat(s_grid, G * O)
    T_rep = jnp.tile(jnp.repeat(T_grid, O), s_grid.shape[0])
    return s_rep, T_rep


@partial(jax.jit, static_argnums=(0, 5))
def _sweep_device(
    cfg: TMConfig,
    s_rep: jax.Array,    # [R] f32
    T_rep: jax.Array,    # [R] i32
    off_sets,            # (off_x [O,n,f], off_y [O,n], off_valid [O,n]|None)
    val_sets,            # (val_x [O,m,f], val_y [O,m])
    n_epochs: int,
    keys: jax.Array,     # [O] keys
) -> jax.Array:
    """The entire sweep as one compiled program. [R] validation accuracy."""
    off_x, off_y, off_valid = off_sets
    val_x, val_y = val_sets
    R = s_rep.shape[0]

    rt = tm_mod.init_runtime(cfg)._replace(s=s_rep, T=T_rep)
    state = replicate_state(cfg, R)
    state = fb_mod.train_epochs_replicated(
        cfg, state, rt, off_x, off_y, keys, n_epochs, valid=off_valid
    )
    return acc_mod.analyze_replicated(cfg, state, rt, val_x, val_y)


def _analyze_all_replicated(cfg, state, ctl: mgr.CycleCtl) -> jax.Array:
    # ONE clause contraction for the whole three-set analysis block (the
    # ROADMAP system-path item): the include bank streams once per cycle.
    s = ctl.sets
    return acc_mod.analyze_sets_replicated(cfg, state, ctl.rt, [
        (s.offline_x, s.offline_y, s.offline_valid),
        (s.validation_x, s.validation_y, s.validation_valid),
        (s.online_x, s.online_y, s.online_valid),
    ])                                                 # [O, 3]


@partial(jax.jit, static_argnums=(0, 1, 5))
def _system_device(
    cfg: TMConfig,
    sys_cfg: mgr.SystemConfig,
    states: TMState,     # leaves [O, ...]
    rt: TMRuntime,       # shared (unreplicated) leaves
    sets: mgr.Sets,      # leaves [O, ...]
    schedule: mgr.Schedule,
    keys: jax.Array,     # [O] keys
):
    """Replica-parallel Fig-3 flow: every ordering advances per step in one
    fused plane instead of one vmapped program per ordering."""
    ks = jax.vmap(jax.random.split)(keys)              # [O, 2, key]
    k_off, k_onl = ks[:, 0], ks[:, 1]

    # --- offline training phase (cycle index -1) ---
    ctl0 = schedule(jnp.int32(-1), rt, sets)
    train_valid = ctl0.sets.offline_train_valid
    if train_valid is None:
        train_valid = ctl0.sets.offline_valid
    else:
        train_valid = train_valid & ctl0.sets.offline_valid
    state = fb_mod.train_epochs_replicated(
        cfg, states, ctl0.rt,
        ctl0.sets.offline_x, ctl0.sets.offline_y,
        k_off, sys_cfg.n_offline_epochs,
        valid=train_valid,
    )
    acc0 = _analyze_all_replicated(cfg, state, ctl0)

    # --- online cycles ---
    def body(carry, cycle):
        st = carry
        ctl = schedule(cycle, rt, sets)
        k = jax.vmap(lambda kk: jax.random.fold_in(kk, cycle))(k_onl)
        new_st, act = fb_mod.train_datapoints_replicated(
            cfg, st, ctl.rt, ctl.sets.online_x, ctl.sets.online_y, k,
            valid=ctl.sets.online_valid,
        )
        st = jax.tree.map(
            lambda a, b: jnp.where(ctl.online_enabled, a, b), new_st, st
        )
        accs = _analyze_all_replicated(cfg, st, ctl)
        activity = jnp.where(ctl.online_enabled, jnp.mean(act, axis=0), 0.0)
        return st, (accs, activity)

    cycles = jnp.arange(sys_cfg.n_online_cycles, dtype=jnp.int32)
    state, (accs, activity) = jax.lax.scan(body, state, cycles)
    accuracies = jnp.concatenate(
        [acc0[:, None], jnp.moveaxis(accs, 0, 1)], axis=1
    )                                                  # [O, 1+cycles, 3]
    return state, accuracies, jnp.moveaxis(activity, 0, 1)


@dataclasses.dataclass(frozen=True)
class CrossValRun:
    """The cross-validation engine: ONE compiled program per sweep.

    ``mesh`` (optional) shards the replica axis of every input over the
    mesh's ``data`` axes via :func:`distributed.sharding.replica_shardings`
    before launch — pod-scale design-space exploration with zero changes to
    the compiled program.
    """

    cfg: TMConfig
    mesh: Optional[Mesh] = None

    def _on_mesh(self):
        """The mesh as JAX's context mesh around a device program, so the
        Pallas kernels run per device under ``shard_map`` (kernels/ops.py)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _put(self, tree, n_replicas: Optional[int] = None):
        if self.mesh is None:
            return tree
        # Shard only the full-R (grid-major) axis; per-data-stream leaves
        # (leading D < R) replicate so every replica's r % D gather stays
        # device-local (no cross-device collectives inside the sweep).
        sh = shard_mod.replica_shardings(
            tree, self.mesh, n_replicas=n_replicas
        )
        return jax.tree.map(jax.device_put, tree, sh)

    def sweep(
        self,
        off_x, off_y,      # [O, n, f] / [O, n] — per-ordering offline sets
        val_x, val_y,      # [O, m, f] / [O, m] — per-ordering validation sets
        s_values, T_values,
        *,
        n_epochs: int = 10,
        seed: int = 0,
        offline_valid=None,  # [O, n] bool — masked-out rows skipped
    ) -> SweepResult:
        """The full (s x T x orderings) sweep as one program.

        Bit-identical to looping ``hpsearch._one_cell`` over every cell with
        the same per-ordering keys (``split(PRNGKey(seed), O)``).
        """
        O = off_x.shape[0]
        s_rep, T_rep = grid_layout(s_values, T_values, O)
        S, G = len(np.atleast_1d(s_values)), len(np.atleast_1d(T_values))
        R = S * G * O
        keys = jax.random.split(jax.random.PRNGKey(seed), O)

        off = (
            jnp.asarray(off_x, bool), jnp.asarray(off_y, jnp.int32),
            None if offline_valid is None else jnp.asarray(offline_valid, bool),
        )
        val = (jnp.asarray(val_x, bool), jnp.asarray(val_y, jnp.int32))
        s_rep, T_rep, off, val, keys = self._put(
            (s_rep, T_rep, off, val, keys), n_replicas=R
        )

        t0 = time.perf_counter()
        with self._on_mesh():
            acc = _sweep_device(self.cfg, s_rep, T_rep, off, val, n_epochs,
                                keys)
        acc = jax.block_until_ready(acc)
        wall = time.perf_counter() - t0

        val_accuracy = acc.reshape(S, G, O)
        return SweepResult(
            s_grid=np.asarray(s_values, dtype=np.float32),
            T_grid=np.asarray(T_values, dtype=np.int32),
            val_accuracy=val_accuracy,
            mean_accuracy=jnp.mean(val_accuracy, axis=-1),
            replicas=R,
            wall_s=wall,
            replicas_per_s=R / max(wall, 1e-9),
        )

    def system(
        self,
        sys_cfg: mgr.SystemConfig,
        states: TMState,     # leaves [O, ...]
        rt: TMRuntime,       # shared leaves (s/T scalars, masks)
        sets: mgr.Sets,      # leaves [O, ...]
        schedule: mgr.Schedule,
        keys: jax.Array,     # [O] keys
    ) -> SystemResult:
        """All cross-validation orderings through the Fig-3 system flow.

        The replica-parallel successor of vmapping ``manager.run_system``:
        same accuracies/activity bit-for-bit, one fused plane per datapoint.
        """
        O = keys.shape[0]
        states, sets, keys = self._put((states, sets, keys), n_replicas=O)
        t0 = time.perf_counter()
        with self._on_mesh():
            state, accs, activity = _system_device(
                self.cfg, sys_cfg, states, rt, sets, schedule, keys
            )
        accs = jax.block_until_ready(accs)
        return SystemResult(
            state=state,
            accuracies=accs,
            activity=activity,
            replicas=O,
            wall_s=time.perf_counter() - t0,
        )
