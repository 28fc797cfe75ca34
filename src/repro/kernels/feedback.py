"""Pallas TPU kernel: fused Type I/II TA-bank update.

The FPGA applies feedback to every TA in one clock. Here the whole
(class x clause x literal) plane is one elementwise VPU pass, fused so the
TA states are read+written exactly once per datapoint and no [CJ, L]
intermediates (deltas, masks) ever round-trip to HBM.

Layout: rows = flattened (class, clause); lanes = literals. Per-clause
control (clause output, Type I/II selection) rides the first three lanes
of a per-replica [CJ, LANES] int8 control block; probabilities ride a
per-replica [1, LANES] f32 vector (lane 0 = p_strengthen, lane 1 =
p_erase) and broadcast inside the kernel. Both are built in one dense
write each (``clause_eval.lane_plane``), never as column scatters, which
a v5e pays for as whole-plane passes.

A Pallas grid step has a fixed cost (about 0.3 µs on a v5e), so at narrow
widths a step updates a block of RB tenants' whole planes
(:func:`tenant_block`), taken unpadded as full-dim blocks. Where one
tenant's plane is too wide for that (MNIST-scale widths) or the data
streams are shared (RB = 1), the plane is tiled into TPU-aligned 32-row
blocks, and the literal axis too (``BLK_L`` lanes per block, the same
scheme as ``clause_eval.py``): the update is elementwise along literals,
so literal blocks are independent grid steps — no accumulation — and the
f32 uniforms block (the widest operand) stays bounded in VMEM regardless
of datapath width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.clause_eval import _pad_l, lane_plane

BLK_CJ = 32
LANES = 128


def _kernel_replicated(n_states: int, ta_ref, lit_ref, ctl_ref, u_ref, p_ref,
                       out_ref, *, per_clause: bool = False):
    # Refs carry a leading replica-block dim of RB tenants: [RB, rows, lanes]
    # planes, one literal row [RB, 1, lanes] and one p row [RB, 1, LANES].
    ta = ta_ref[...].astype(jnp.int32)        # [RB, BLK, Lp]
    # [RB, 1, Lp] bool, or with ``per_clause`` one row per clause [1, BLK, Lp],
    # widened first: Mosaic cannot relayout an int8-tiled mask of that shape
    lit = (lit_ref[...].astype(jnp.int32) if per_clause else lit_ref[...]) != 0
    ctl = ctl_ref[...]                        # [RB, BLK, LANES] int8
    u = u_ref[...]                            # [RB, BLK, Lp] f32
    p = p_ref[...]                            # [RB, 1, LANES] f32 (per-replica)

    c_out = ctl[:, :, 0:1] != 0               # [RB, BLK, 1]
    t1 = ctl[:, :, 1:2] != 0
    t2 = ctl[:, :, 2:3] != 0

    p_strengthen = p[:, 0:1, 0:1]             # broadcasts over each plane
    p_erase = p[:, 0:1, 1:2]

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


# VMEM that one grid step's double-buffered blocks may take when the step
# holds whole tenant planes: a few MB, under v5e's 16 MB scoped default,
# leaving room for the body's int32 temporaries of the same planes.
VMEM_BLOCKS = 4 << 20


def _vmem_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a [rows, cols] plane in the native tiling: 128 lanes
    by 8 sublanes of 32-bit words (16 of int16, 32 of int8)."""
    sub = 32 // itemsize
    return -(-rows // sub) * sub * -(-cols // LANES) * LANES * itemsize


def tenant_block(R: int, D: int, cj: int, L: int, dtype) -> int:
    """RB, the tenants one grid step of :func:`feedback_plane_replicated`
    updates: the largest power of two that divides R and D (so block ``b``
    reads data block ``b % (D // RB)`` and the ``r % D`` rule holds) whose
    double-buffered whole-plane blocks fit :data:`VMEM_BLOCKS`.

    1 keeps the tiled grid: a stream shared by all replicas (D = 1), or a
    plane too wide for one tenant's blocks to fit (f=784)."""
    item = jnp.dtype(dtype).itemsize
    per_tenant = 2 * (2 * _vmem_bytes(cj, L, item)      # bank in and out
                      + _vmem_bytes(cj, L, 4)            # uniforms
                      + _vmem_bytes(cj, LANES, 1)        # ctl
                      + _vmem_bytes(1, L, 1)             # literals
                      + _vmem_bytes(1, LANES, 4))        # p
    rb = 1
    while (R % (2 * rb) == 0 and D % (2 * rb) == 0
           and 2 * rb * per_tenant <= VMEM_BLOCKS):
        rb *= 2
    return rb


def grid_steps(R: int, D: int, cj: int, L: int, dtype) -> int:
    """Grid steps of one :func:`feedback_plane_replicated` launch."""
    rb = tenant_block(R, D, cj, L, dtype)
    if rb > 1:
        return R // rb
    Lp, blk_l = _pad_l(L)
    return R * -(-cj // BLK_CJ) * (Lp // blk_l)


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

    The FPGA's per-datapoint feedback plane replicated spatially, the TPU
    form of the paper's cross-validation re-runs (one machine is R = 1).
    The literal/uniform operands are *factored*: the BlockSpec index maps
    send replica ``r`` to data row ``r % D``, so draws shared across a
    hyperparameter grid are stored once, not R/D times.

    Two grids, chosen by :func:`tenant_block` from the shapes alone:

    * RB > 1 (narrow planes, one stream per RB tenants or more): a grid of
      R / RB steps, each updating RB tenants' whole unpadded [CJ, L]
      planes (full-dim blocks), so no padded copy of the bank or the
      uniforms is made and the grid's fixed cost per step is paid R / RB
      times, not once per 32x128 tile.
    * RB = 1: a grid over (replica, clause-block, literal-block) of padded
      32 x ``blk_l`` tiles, which bounds VMEM at any datapath width.

    Returns new ta_state [R, CJ, L].
    """
    R, cj, L = ta_state.shape
    D = literals.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    dt = ta_state.dtype
    p = lane_plane([p_strengthen[:, None], p_erase[:, None]], jnp.float32)
    kernel = functools.partial(_kernel_replicated, n_states)

    rb = tenant_block(R, D, cj, L, dt)
    if rb > 1:
        nd = D // rb
        plane = pl.BlockSpec((rb, cj, L), lambda b: (b, 0, 0))
        data = pl.BlockSpec((rb, cj, L), lambda b: (b % nd, 0, 0))
        return pl.pallas_call(
            kernel,
            grid=(R // rb,),
            in_specs=[
                plane,
                pl.BlockSpec((rb, 1, L), lambda b: (b % nd, 0, 0)),
                pl.BlockSpec((rb, cj, LANES), lambda b: (b, 0, 0)),
                data,
                pl.BlockSpec((rb, 1, LANES), lambda b: (b, 0, 0)),
            ],
            out_specs=plane,
            out_shape=jax.ShapeDtypeStruct((R, cj, L), dt),
            interpret=interpret,
            name="feedback_plane_replicated",
        )(ta_state, literals.astype(jnp.int8)[:, None, :],
          lane_plane([clause_out, type1_sel, type2_sel], jnp.int8),
          u.astype(jnp.float32), p)

    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Lp, blk_l = _pad_l(L)
    ta = jnp.ones((R, cjp, Lp), dtype=dt).at[:, :cj, :L].set(ta_state)
    lit = jnp.zeros((D, 1, Lp), dtype=jnp.int8).at[:, 0, :L].set(
        literals.astype(jnp.int8)
    )
    ctl = lane_plane([clause_out, type1_sel, type2_sel], jnp.int8, rows=cjp)
    # Pad u with 1.0 so padded lanes never draw an action.
    up = jnp.ones((D, cjp, Lp), dtype=jnp.float32).at[:, :cj, :L].set(
        u.astype(jnp.float32)
    )

    out = pl.pallas_call(
        kernel,
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
        name="feedback_plane_replicated",
    )(ta, lit, ctl, up, p)
    return out[:, :cj, :L]


# Clause rows per block of the patch feedback kernel. Its operands are
# [rows, literals] planes, taken unpadded: a block spans the whole literal
# axis, and the last block of clauses may run past the plane's end (Pallas
# drops what falls outside; rows are independent). So the wide uniform
# plane is read where it lies, with no padded copy.
BLK_PCJ = 512


@functools.partial(
    jax.jit, static_argnames=("n_states", "interpret")
)
def feedback_patch_replicated(
    ta_state: jax.Array,    # [R, CJ, L] int8/int16
    literals: jax.Array,    # [R, CJ, L] bool — each clause's chosen patch
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
    """:func:`feedback_plane_replicated` for patch machines: the literal
    operand is one row per clause (the literals of the patch the clause
    chose), blocked like the bank, instead of one row per replica. The
    kernel body is the same. Returns new ta_state [R, CJ, L]."""
    R, cj, L = ta_state.shape
    D = u.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    blk = min(BLK_PCJ, -(-cj // BLK_CJ) * BLK_CJ)

    ctl = lane_plane([clause_out, type1_sel, type2_sel], jnp.int8)
    p = lane_plane([p_strengthen[:, None], p_erase[:, None]], jnp.float32)

    plane = pl.BlockSpec((1, blk, L), lambda r, i: (r, i, 0))
    return pl.pallas_call(
        functools.partial(_kernel_replicated, n_states, per_clause=True),
        grid=(R, pl.cdiv(cj, blk)),
        in_specs=[
            plane,
            plane,
            pl.BlockSpec((1, blk, LANES), lambda r, i: (r, i, 0)),
            pl.BlockSpec((1, blk, L), lambda r, i: (r % D, i, 0)),
            pl.BlockSpec((1, 1, LANES), lambda r, i: (r, 0, 0)),
        ],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((R, cj, L), ta_state.dtype),
        interpret=interpret,
        name="feedback_patch_replicated",
    )(ta_state, literals.astype(jnp.int8), ctl, u.astype(jnp.float32), p)
