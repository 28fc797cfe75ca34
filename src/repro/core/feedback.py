"""TM learning: feedback selection + TA updates (paper §2, §4).

The FPGA applies inference *and* feedback for all clauses/TAs of a datapoint in
two clock cycles; here the same plane of work is a single fused vectorized
update, and datapoints stream through ``lax.scan`` preserving the hardware's
serial semantics (feedback at step t sees TA state from t-1).

Runtime hyperparameters ``s`` and ``T`` are traced scalars carried in
:class:`~repro.core.tm.TMRuntime` — changing them (the paper's I/O ports) never
triggers re-compilation.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tm as tm_mod
from repro.core.tm import TMConfig, TMRuntime, TMState
from repro.kernels import dispatch


# fold_in datum of a patch machine's patch-choice key: the row key folded
# with it, apart from the row key's (selection, uniforms) split
PATCH_DRAW = 1


class StepAux(NamedTuple):
    """Per-step observability (feeds the accuracy/energy analysis blocks)."""

    votes: jax.Array       # [C] int32 class sums (training-mode clause outputs)
    predicted: jax.Array   # scalar int32 argmax class (inference-mode)
    correct: jax.Array     # scalar bool
    activity: jax.Array    # scalar f32 — fraction of TAs that changed state
                           # (the clock-gating/energy analogue, DESIGN.md §2)


def _selection_core(
    cfg: TMConfig,
    T: jax.Array,            # scalar i32
    clause_mask: jax.Array,  # [J] bool
    class_mask: jax.Array,   # [C] bool
    votes: jax.Array,        # [C] int32
    y: jax.Array,            # scalar int32 target class
    key: jax.Array,
):
    """One replica's feedback-type selection: the target class y gets
    P(feedback) = (T - clip(v_y)) / 2T (positive-polarity clauses Type I,
    negative Type II); one sampled non-target class ny gets
    P(feedback) = (T + clip(v_ny)) / 2T (positive Type II, negative
    Type I)."""
    k_neg, k_t, k_n = jax.random.split(key, 3)
    Tf = T.astype(jnp.float32)
    C, J = cfg.max_classes, cfg.max_clauses

    # Sample a non-target active class uniformly (the paper's multi-class rule).
    neg_ok = class_mask & (jnp.arange(C) != y)
    logits = jnp.where(neg_ok, 0.0, -jnp.inf)
    ny = jax.random.categorical(k_neg, logits)

    v = jnp.clip(votes, -T, T).astype(jnp.float32)
    p_t = (Tf - v[y]) / (2.0 * Tf)
    p_n = (Tf + v[ny]) / (2.0 * Tf)

    sel_t = (jax.random.uniform(k_t, (J,)) < p_t) & clause_mask
    sel_n = (jax.random.uniform(k_n, (J,)) < p_n) & clause_mask

    pos = tm_mod.clause_polarity(cfg) > 0  # [J]
    onehot_y = jax.nn.one_hot(y, C, dtype=bool)
    onehot_n = jax.nn.one_hot(ny, C, dtype=bool)

    type1 = (
        onehot_y[:, None] & (sel_t & pos)[None, :]
        | onehot_n[:, None] & (sel_n & ~pos)[None, :]
    )
    type2 = (
        onehot_y[:, None] & (sel_t & ~pos)[None, :]
        | onehot_n[:, None] & (sel_n & pos)[None, :]
    )
    # Inactive classes never receive feedback (over-provisioning, §3.1.1).
    type1 = type1 & class_mask[:, None]
    type2 = type2 & class_mask[:, None]
    return type1, type2


def train_update(
    cfg: TMConfig,
    state: TMState,
    rt: TMRuntime,
    x: jax.Array,
    y: jax.Array,
    key: jax.Array,
) -> tuple[TMState, jax.Array, jax.Array]:
    """One supervised datapoint's TA-bank update — no monitoring pass.

    The learning half of the paper's 2-clock-cycle datapath: one fused plane
    of (C x J x 2f) elementwise work plus two small reductions. Returns
    (new_state, training-mode votes [C], activity scalar). Consumers that
    want per-step inference-mode monitoring use :func:`train_step`; the
    drain (``online._consume_many_replicated``) hoists monitoring out of
    the serial scan and runs it once per chunk through the batch clause
    kernel.

    One machine is the R = D = 1 slice of :func:`train_update_replicated`,
    which splits ``key`` into the selection and uniform draws.
    """
    st, votes, activity = train_update_replicated(
        cfg, TMState(ta_state=state.ta_state[None]), rt, x[None],
        y[None], key[None])
    return TMState(ta_state=st.ta_state[0]), votes[0], activity[0]


def train_step(
    cfg: TMConfig,
    state: TMState,
    rt: TMRuntime,
    x: jax.Array,
    y: jax.Array,
    key: jax.Array,
) -> tuple[TMState, StepAux]:
    """One supervised datapoint: inference + feedback for all clauses/TAs."""
    new_state, votes, activity = train_update(cfg, state, rt, x, y, key)

    # Inference-mode prediction for monitoring (empty clauses vote 0).
    _, votes_inf = tm_mod.forward(cfg, state, rt, x, training=False)
    votes_inf = jnp.where(rt.class_mask, votes_inf, jnp.iinfo(jnp.int32).min)
    pred = jnp.argmax(votes_inf).astype(jnp.int32)

    aux = StepAux(
        votes=votes,
        predicted=pred,
        correct=(pred == y),
        activity=activity,
    )
    return new_state, aux


def train_datapoints(
    cfg: TMConfig,
    state: TMState,
    rt: TMRuntime,
    xs: jax.Array,       # [n, f] bool
    ys: jax.Array,       # [n] int32
    key: jax.Array,
    valid: jax.Array | None = None,  # [n] bool — masked-out rows are skipped
) -> tuple[TMState, StepAux]:
    """Stream datapoints serially (lax.scan), matching the FPGA's row order.

    ``valid`` lets fixed-shape sets carry variable row counts (class filtering,
    partial sets) without recompilation: invalid rows leave state untouched.
    """
    n = xs.shape[0]
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)

    def body(carry, inp):
        st = carry
        x, y, v, k = inp
        new_st, aux = train_step(cfg, st, rt, x, y, k)
        st = jax.tree.map(lambda a, b: jnp.where(v, a, b), new_st, st)
        aux = aux._replace(
            activity=jnp.where(v, aux.activity, 0.0),
            correct=aux.correct & v,
        )
        return st, aux

    keys = jax.random.split(key, n)
    final, auxes = jax.lax.scan(body, state, (xs, ys, valid, keys))
    return final, auxes


# ---------------------------------------------------------------------------
# Replica-parallel training (cross-validation x hyperparameter sweep axis).
#
# R independent TMs advance one datapoint per step in ONE fused plane. Layout
# rule (mirrors the kernel contract in kernels/dispatch.py): per-replica state
# and control carry a leading R; per-data-stream operands (xs, ys, keys) carry
# a leading D with D | R, replica r consuming stream r % D. A hyperparameter
# sweep lays replicas out grid-major/ordering-minor so the (s, T) grid shares
# each ordering's data and RNG draws instead of tiling them R/D-fold; RNG
# streams are per data replica, so results are bit-identical to running each
# replica through train_update alone.
# ---------------------------------------------------------------------------


def _replica_counts(state: TMState, xs: jax.Array) -> tuple[int, int]:
    R = state.ta_state.shape[0]
    D = xs.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    return R, D


def train_update_replicated(
    cfg: TMConfig,
    state: TMState,    # leaves [R, ...]
    rt: TMRuntime,     # s/T scalar or [R]; masks shared (unreplicated) shapes
    x: jax.Array,      # [D, f] bool
    y: jax.Array,      # [D] int32
    key: jax.Array,    # [D] keys
) -> tuple[TMState, jax.Array, jax.Array]:
    """One datapoint's TA-bank update for all R replicas at once.

    Replica ``r`` performs exactly the computation of one machine
    (:func:`train_update`, the R = D = 1 slice) with data stream ``r % D``
    and hyperparameters ``s[r]``/``T[r]`` — bit-for-bit, including the RNG
    draws: ``key[d]`` splits into the feedback-selection and uniform keys,
    so streams are keyed per data replica, shared across a hyperparameter
    grid exactly as re-running :func:`train_update` per cell would. Returns (new_state,
    votes [R, C], activity [R]); unused outputs are DCE'd under jit.

    A patch machine (``cfg.patched``, the Convolutional TM) evaluates each
    clause over the row's patches and gives a firing clause feedback from
    the literals of one patch it fired on, drawn with one float32 uniform
    per clause: ``uniform(fold_in(row_key, PATCH_DRAW), (C, J))``, the row
    key being ``key[d]`` (DESIGN.md §18). Every other draw is the plain
    machine's.
    """
    R, D = _replica_counts(state, x)
    H = R // D
    k2 = jax.vmap(jax.random.split)(key)        # [D, 2, key]
    k_sel, k_u = k2[:, 0], k2[:, 1]

    backend = dispatch.resolve(cfg.backend)
    if cfg.patched:
        include = tm_mod.ta_actions(cfg, state, rt)
        u_patch = jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, PATCH_DRAW),
            (cfg.max_classes, cfg.max_clauses), dtype=jnp.float32,
        ))(key)                                 # [D, C, J]
        clauses_tr, lits = tm_mod.patch_training_clauses(
            cfg, include, rt, x, u_patch)       # [R, C, J], [R, C, J, L]
        feedback = backend.feedback_patch_replicated
    else:
        lits = tm_mod.make_literals(x)              # [D, L]
        include = tm_mod.ta_actions(cfg, state, rt)  # [R, C, J, L] (masks broadcast)

        clauses_tr = backend.clause_eval_replicated(include, lits, training=True)
        clauses_tr = clauses_tr & rt.clause_mask[None, None, :]
        feedback = backend.feedback_step_replicated
    votes = tm_mod.class_sums(cfg, clauses_tr)  # [R, C]

    T_rep = jnp.broadcast_to(jnp.asarray(rt.T, jnp.int32), (R,))
    sel = partial(_selection_core, cfg)
    type1, type2 = jax.vmap(sel, in_axes=(0, None, None, 0, 0, 0))(
        T_rep, rt.clause_mask, rt.class_mask,
        votes, jnp.tile(y, H), jnp.tile(k_sel, (H, 1)),
    )

    u = jax.vmap(
        lambda k: jax.random.uniform(
            k, (cfg.max_classes, cfg.max_clauses, cfg.n_literals),
            dtype=jnp.float32,
        )
    )(k_u)                                      # [D, C, J, L] — factored draws

    new_ta = feedback(
        state.ta_state, lits, clauses_tr, type1, type2, u,
        s=jnp.broadcast_to(jnp.asarray(rt.s, jnp.float32), (R,)),
        n_states=cfg.n_states, s_policy=cfg.s_policy,
        boost_true_positive=cfg.boost_true_positive,
    )

    activity = jnp.mean(
        (new_ta != state.ta_state).astype(jnp.float32), axis=(1, 2, 3)
    )
    return TMState(ta_state=new_ta), votes, activity


def train_datapoints_replicated(
    cfg: TMConfig,
    state: TMState,    # leaves [R, ...]
    rt: TMRuntime,
    xs: jax.Array,     # [D, n, f] bool
    ys: jax.Array,     # [D, n] int32
    key: jax.Array,    # [D] keys
    valid: jax.Array | None = None,  # [D, n] bool
) -> tuple[TMState, jax.Array]:
    """Stream the data sets serially while updating all R replicas per step.

    The replica-parallel form of :func:`train_datapoints`: ONE ``lax.scan``
    over datapoint index (the FPGA's row order, preserving feedback-sees-
    state-from-t-1 semantics) whose body advances every replica in a single
    fused plane. Returns (final_state, activity [n, R]).
    """
    R, D = _replica_counts(state, xs)
    H = R // D
    n = xs.shape[1]
    if valid is None:
        valid = jnp.ones((D, n), dtype=bool)

    keys = jax.vmap(lambda k: jax.random.split(k, n))(key)  # [D, n, key]
    keys = jnp.swapaxes(keys, 0, 1)                         # [n, D, key]

    def body(carry, inp):
        st = carry
        x, y, v, k = inp               # [D, f], [D], [D], [D] keys
        new_st, _, act = train_update_replicated(cfg, st, rt, x, y, k)
        vR = jnp.tile(v, H)            # replica r gated by stream r % D
        st = jax.tree.map(
            lambda a, b: jnp.where(
                vR.reshape((R,) + (1,) * (a.ndim - 1)), a, b
            ),
            new_st, st,
        )
        return st, jnp.where(vR, act, 0.0)

    final, activity = jax.lax.scan(
        body, state,
        (jnp.swapaxes(xs, 0, 1), jnp.swapaxes(ys, 0, 1),
         jnp.swapaxes(valid, 0, 1), keys),
    )
    return final, activity


@partial(jax.jit, static_argnums=0)
def train_epochs_replicated(
    cfg: TMConfig,
    state: TMState,    # leaves [R, ...]
    rt: TMRuntime,
    xs: jax.Array,     # [D, n, f]
    ys: jax.Array,     # [D, n]
    key: jax.Array,    # [D] keys
    n_epochs: int | jax.Array,
    valid: jax.Array | None = None,
) -> TMState:
    """Replica-parallel :func:`train_epochs`: the whole sweep's offline
    training is one compiled program scanning the dataset once per epoch."""
    n_epochs = jnp.asarray(n_epochs, dtype=jnp.int32)

    def body(i, st):
        k = jax.vmap(lambda kk: jax.random.fold_in(kk, i))(key)
        new_st, _ = train_datapoints_replicated(cfg, st, rt, xs, ys, k, valid)
        return new_st

    return jax.lax.fori_loop(0, n_epochs, body, state)


@partial(jax.jit, static_argnums=0)
def train_epochs(
    cfg: TMConfig,
    state: TMState,
    rt: TMRuntime,
    xs: jax.Array,
    ys: jax.Array,
    key: jax.Array,
    n_epochs: int | jax.Array,
    valid: jax.Array | None = None,
) -> TMState:
    """Repeat the dataset for a (traced) number of epochs.

    ``n_epochs`` is a runtime value: the scan runs to a static max derived from
    the array only when traced as python int; otherwise use fori_loop.
    """
    n_epochs = jnp.asarray(n_epochs, dtype=jnp.int32)

    def body(i, st):
        k = jax.random.fold_in(key, i)
        new_st, _ = train_datapoints(cfg, st, rt, xs, ys, k, valid)
        return new_st

    return jax.lax.fori_loop(0, n_epochs, body, state)
