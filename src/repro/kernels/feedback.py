"""Pallas TPU kernel: fused Type I/II TA-bank update.

The FPGA applies feedback to every TA in one clock. Here the whole
(class x clause x literal) plane is one elementwise VPU pass, fused so the
TA states are read+written exactly once per datapoint and no [CJ, L]
intermediates (deltas, masks) ever round-trip to HBM.

Layout: rows = flattened (class, clause); lanes = literals. Per-clause
control (clause output, Type I/II selection) is packed into the first three
columns of a [CJ, LANES] int8 control block so every operand block is
TPU-tile aligned; probabilities ride a [1, LANES] f32 vector (col 0 =
p_strengthen, col 1 = p_erase) and broadcast inside the kernel.

At MNIST-scale widths the literal axis is tiled too (``BLK_L`` lanes per
block, the same scheme as ``clause_eval.py``): the update is elementwise
along literals, so literal blocks are independent grid steps — no
accumulation — and the f32 uniforms block (the widest operand) stays
bounded in VMEM regardless of datapath width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.clause_eval import _pad_l

BLK_CJ = 32
LANES = 128


def _kernel(n_states: int, ta_ref, lit_ref, ctl_ref, u_ref, p_ref, out_ref):
    ta = ta_ref[...].astype(jnp.int32)        # [BLK, Lp]
    lit = lit_ref[...] != 0                   # [1, Lp] bool
    ctl = ctl_ref[...]                        # [BLK, LANES] int8
    u = u_ref[...]                            # [BLK, Lp] f32
    p = p_ref[...]                            # [1, LANES] f32

    c_out = ctl[:, 0:1] != 0                  # [BLK, 1]
    t1 = ctl[:, 1:2] != 0
    t2 = ctl[:, 2:3] != 0

    p_strengthen = p[0:1, 0:1]                # [1, 1] broadcasts over the plane
    p_erase = p[0:1, 1:2]

    include = ta > n_states
    strengthen = c_out & lit                  # clause fired & literal true
    d1 = jnp.where(
        strengthen,
        (u < p_strengthen).astype(jnp.int32),
        -((u < p_erase).astype(jnp.int32)),
    )
    d2 = (c_out & (~lit) & (~include)).astype(jnp.int32)
    delta = jnp.where(t1, d1, 0) + jnp.where(t2, d2, 0)
    out_ref[...] = jnp.clip(ta + delta, 1, 2 * n_states).astype(out_ref.dtype)


def _kernel_replicated(n_states: int, ta_ref, lit_ref, ctl_ref, u_ref, p_ref,
                       out_ref):
    # Refs carry a leading replica-block dim of 1: [1, BLK, Lp] / [1, 1, Lp].
    ta = ta_ref[...].astype(jnp.int32)        # [1, BLK, Lp]
    lit = lit_ref[...] != 0                   # [1, 1, Lp] bool
    ctl = ctl_ref[...]                        # [1, BLK, LANES] int8
    u = u_ref[...]                            # [1, BLK, Lp] f32
    p = p_ref[...]                            # [1, 1, LANES] f32 (per-replica)

    c_out = ctl[:, :, 0:1] != 0               # [1, BLK, 1]
    t1 = ctl[:, :, 1:2] != 0
    t2 = ctl[:, :, 2:3] != 0

    p_strengthen = p[0:1, 0:1, 0:1]           # broadcasts over the plane
    p_erase = p[0:1, 0:1, 1:2]

    include = ta > n_states
    strengthen = c_out & lit
    d1 = jnp.where(
        strengthen,
        (u < p_strengthen).astype(jnp.int32),
        -((u < p_erase).astype(jnp.int32)),
    )
    d2 = (c_out & (~lit) & (~include)).astype(jnp.int32)
    delta = jnp.where(t1, d1, 0) + jnp.where(t2, d2, 0)
    out_ref[...] = jnp.clip(ta + delta, 1, 2 * n_states).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_states", "interpret")
)
def feedback_plane(
    ta_state: jax.Array,    # [CJ, L] int8/int16
    literals: jax.Array,    # [L] bool
    clause_out: jax.Array,  # [CJ] bool
    type1_sel: jax.Array,   # [CJ] bool
    type2_sel: jax.Array,   # [CJ] bool
    u: jax.Array,           # [CJ, L] f32 uniforms
    p_strengthen: jax.Array,  # scalar f32
    p_erase: jax.Array,       # scalar f32
    *,
    n_states: int,
    interpret: bool,
) -> jax.Array:
    """Fused TA update over the flattened plane. Returns new ta_state [CJ, L]."""
    cj, L = ta_state.shape
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Lp, blk_l = _pad_l(L)
    dt = ta_state.dtype

    ta = jnp.ones((cjp, Lp), dtype=dt).at[:cj, :L].set(ta_state)
    lit = jnp.zeros((1, Lp), dtype=jnp.int8).at[0, :L].set(
        literals.astype(jnp.int8)
    )
    ctl = jnp.zeros((cjp, LANES), dtype=jnp.int8)
    ctl = ctl.at[:cj, 0].set(clause_out.astype(jnp.int8))
    ctl = ctl.at[:cj, 1].set(type1_sel.astype(jnp.int8))
    ctl = ctl.at[:cj, 2].set(type2_sel.astype(jnp.int8))
    # Pad u with 1.0 so padded lanes never draw an action.
    up = jnp.ones((cjp, Lp), dtype=jnp.float32).at[:cj, :L].set(
        u.astype(jnp.float32)
    )
    p = jnp.zeros((1, LANES), dtype=jnp.float32)
    p = p.at[0, 0].set(p_strengthen).at[0, 1].set(p_erase)

    out = pl.pallas_call(
        functools.partial(_kernel, n_states),
        grid=(cjp // BLK_CJ, Lp // blk_l),
        in_specs=[
            pl.BlockSpec((BLK_CJ, blk_l), lambda i, l: (i, l)),
            pl.BlockSpec((1, blk_l), lambda i, l: (0, l)),
            pl.BlockSpec((BLK_CJ, LANES), lambda i, l: (i, 0)),
            pl.BlockSpec((BLK_CJ, blk_l), lambda i, l: (i, l)),
            pl.BlockSpec((1, LANES), lambda i, l: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLK_CJ, blk_l), lambda i, l: (i, l)),
        out_shape=jax.ShapeDtypeStruct((cjp, Lp), dt),
        interpret=interpret,
    )(ta, lit, ctl, up, p)
    return out[:cj, :L]


@functools.partial(
    jax.jit, static_argnames=("n_states", "interpret")
)
def feedback_plane_replicated(
    ta_state: jax.Array,    # [R, CJ, L] int8/int16
    literals: jax.Array,    # [D, L] bool — replica r reads row r % D
    clause_out: jax.Array,  # [R, CJ] bool
    type1_sel: jax.Array,   # [R, CJ] bool
    type2_sel: jax.Array,   # [R, CJ] bool
    u: jax.Array,           # [D, CJ, L] f32 — replica r reads row r % D
    p_strengthen: jax.Array,  # [R] f32
    p_erase: jax.Array,       # [R] f32
    *,
    n_states: int,
    interpret: bool,
) -> jax.Array:
    """R independent TA banks updated in ONE kernel launch.

    2-D grid over (replica, clause-block): the FPGA's per-datapoint feedback
    plane replicated spatially, the TPU form of the paper's cross-validation
    re-runs. A vmap over :func:`feedback_plane` would pad and launch R
    separate planes; here the replica axis is a grid dimension, so the i-th
    clause block of every replica reuses the same tile program, and the
    literal/uniform operands are *factored* — the BlockSpec index map sends
    replica ``r`` to data row ``r % D``, so draws shared across a
    hyperparameter grid are stored once, not R/D times.

    Returns new ta_state [R, CJ, L].
    """
    R, cj, L = ta_state.shape
    D = literals.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Lp, blk_l = _pad_l(L)
    dt = ta_state.dtype

    ta = jnp.ones((R, cjp, Lp), dtype=dt).at[:, :cj, :L].set(ta_state)
    lit = jnp.zeros((D, 1, Lp), dtype=jnp.int8).at[:, 0, :L].set(
        literals.astype(jnp.int8)
    )
    ctl = jnp.zeros((R, cjp, LANES), dtype=jnp.int8)
    ctl = ctl.at[:, :cj, 0].set(clause_out.astype(jnp.int8))
    ctl = ctl.at[:, :cj, 1].set(type1_sel.astype(jnp.int8))
    ctl = ctl.at[:, :cj, 2].set(type2_sel.astype(jnp.int8))
    # Pad u with 1.0 so padded lanes never draw an action.
    up = jnp.ones((D, cjp, Lp), dtype=jnp.float32).at[:, :cj, :L].set(
        u.astype(jnp.float32)
    )
    p = jnp.zeros((R, 1, LANES), dtype=jnp.float32)
    p = p.at[:, 0, 0].set(p_strengthen.astype(jnp.float32))
    p = p.at[:, 0, 1].set(p_erase.astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(_kernel_replicated, n_states),
        grid=(R, cjp // BLK_CJ, Lp // blk_l),
        in_specs=[
            pl.BlockSpec((1, BLK_CJ, blk_l), lambda r, i, l: (r, i, l)),
            pl.BlockSpec((1, 1, blk_l), lambda r, i, l: (r % D, 0, l)),
            pl.BlockSpec((1, BLK_CJ, LANES), lambda r, i, l: (r, i, 0)),
            pl.BlockSpec((1, BLK_CJ, blk_l), lambda r, i, l: (r % D, i, l)),
            pl.BlockSpec((1, 1, LANES), lambda r, i, l: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLK_CJ, blk_l), lambda r, i, l: (r, i, l)),
        out_shape=jax.ShapeDtypeStruct((R, cjp, Lp), dt),
        interpret=interpret,
    )(ta, lit, ctl, up, p)
    return out[:, :cj, :L]
