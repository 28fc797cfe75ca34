"""Pallas TPU kernel: clause evaluation as an MXU matvec.

TPU adaptation of the paper's 2-cycle clause datapath (DESIGN.md §2/§8):
the FPGA computes each clause as a wide AND over included literals with
dedicated LUT trees. On TPU we recast the AND-reduction as an **int8 matmul
on the MXU**:

    violations[c,j] = sum_k include[c,j,k] * (1 - literal[k])
    n_included[c,j] = sum_k include[c,j,k]

    clause fires      <=> violations == 0
    clause is "empty" <=> n_included == 0  (training: fires; inference: not)

Both sums come from ONE [CJ, L] x [L, 2] int8 matmul (rhs columns = ~literals
and ones), so the whole clause plane rides the systolic array instead of the
VPU, and the include bank streams HBM->VMEM exactly once per datapoint.

The block grid tiles the flattened (class x clause) axis AND, at MNIST-scale
widths, the literal axis: up to ``BLK_L`` literal lanes per block (iris
L=32 pads to one 128-lane block; booleanized-MNIST L=1568 runs 4 blocks of
512), with partial sums accumulated into the output block over the
*innermost* grid dimension — the standard Pallas reduction pattern, so
revisits of an output block are consecutive and VMEM residency per block
stays bounded no matter how wide the datapath grows. Accumulation is int32
(``preferred_element_type``): counts are <= L, so there is no headroom
concern at any realistic width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref as _ref

# int8-native TPU tile: 32 sublanes x 128 lanes.
BLK_CJ = 32
LANES = 128
# Literal-axis block: 4 int8 tiles. Widths <= BLK_L keep the pre-tiling
# single-block layout (one l-step); wider datapaths stream literal blocks.
BLK_L = 512


def _pad_l(L: int) -> tuple[int, int]:
    """(padded literal width, literal block) for a datapath of width L."""
    blk = min(BLK_L, -(-L // LANES) * LANES)
    return -(-L // blk) * blk, blk


def lane_plane(cols, dtype, rows: int | None = None) -> jax.Array:
    """Same-shaped ``[..., n]`` columns as the first lanes of a zero
    ``[..., rows, LANES]`` plane (``rows`` defaults to ``n``), built in one
    dense write: the columns stacked on a last axis and padded once.

    The same plane built as ``zeros(...).at[..., :n, k].set(col)`` is one
    scatter per column into a 128-lane tiled array, which a v5e pays for
    nearly as much as a pass over the whole plane.
    """
    x = jnp.stack(cols, axis=-1).astype(dtype)
    n = x.shape[-2]
    rows = n if rows is None else rows
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                   + [(0, rows - n), (0, LANES - len(cols))])


def _kernel_replicated(l_axis: int, inc_ref, rhs_ref, out_ref):
    # Leading length-1 replica block: inc [1, BLK_CJ, blk_l], rhs
    # [1, blk_l, LANES] -> out [1, BLK_CJ, LANES] i32 (shared by both
    # replicated launches), accumulated over the innermost literal axis.
    @pl.when(pl.program_id(l_axis) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        inc_ref[0], rhs_ref[0], preferred_element_type=jnp.int32
    )[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def clause_counts_replicated(
    include: jax.Array,   # [R, CJ, L] int8/bool — per-replica include banks
    literals: jax.Array,  # [D, L] bool — replica r reads row r % D
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(violations [R, CJ] i32, n_included [R, CJ] i32) in ONE kernel launch.

    A grid over (replica, clause-block, literal-block), each replica
    contracting its own include bank against its data stream's literal row
    (rhs column 0: ~literal, the violation counter; column 1: ones, the
    include counter). The rhs BlockSpec maps replica ``r`` to literal row
    ``r % D``, so a hyperparameter grid sharing one ordering's data stream
    stores the rhs once per ordering.
    """
    R, cj, L = include.shape
    D = literals.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Lp, blk_l = _pad_l(L)

    inc = jnp.zeros((R, cjp, Lp), dtype=jnp.int8).at[:, :cj, :L].set(
        include.astype(jnp.int8)
    )
    lit = literals.astype(jnp.int8)
    rhs = lane_plane([1 - lit, jnp.ones_like(lit)], jnp.int8, rows=Lp)

    out = pl.pallas_call(
        functools.partial(_kernel_replicated, 2),
        grid=(R, cjp // BLK_CJ, Lp // blk_l),
        in_specs=[
            pl.BlockSpec((1, BLK_CJ, blk_l), lambda r, i, l: (r, i, l)),
            pl.BlockSpec((1, blk_l, LANES), lambda r, i, l: (r % D, l, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLK_CJ, LANES), lambda r, i, l: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, cjp, LANES), jnp.int32),
        interpret=interpret,
    )(inc, rhs)
    return out[:, :cj, 0], out[:, :cj, 1]


def clause_eval_replicated(
    include: jax.Array,   # [R, C, J, L] bool (post-fault TA actions)
    literals: jax.Array,  # [D, L] bool — replica r reads row r % D
    *,
    training: bool,
    interpret: bool,
) -> jax.Array:
    """Kernel-backed replica-first clause outputs [R, C, J] bool."""
    R, C, J, L = include.shape
    viol, n_inc = clause_counts_replicated(
        include.reshape(R, C * J, L), literals, interpret=interpret
    )
    fired = viol == 0
    empty = n_inc == 0
    out = jnp.where(empty, jnp.bool_(training), fired)
    return out.reshape(R, C, J)


@functools.partial(jax.jit, static_argnames=("interpret",))
def clause_counts_batch_replicated(
    include: jax.Array,   # [R, CJ, L] int8/bool — per-replica include banks
    literals: jax.Array,  # [D, B, L] bool — replica r reads batch r % D
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(violations [R, CJ, B] i32, n_included [R, CJ] i32) in ONE launch.

    A 4-D grid over (replica, clause-block, column-block, literal-block),
    each replica contracting its own include bank against its data
    stream's [L, B+1] rhs: columns 0..B-1 carry ``~literal_b`` (the
    per-datapoint violation counters), column B ones (the include counter,
    datapoint-independent, so one column serves the whole batch). The rhs BlockSpec maps replica ``r`` to stream ``r % D`` — the
    factored layout rule — so a hyperparameter grid sharing one ordering's
    batch stores the rhs once per ordering instead of gathering an R/D-fold
    tiled copy (the take+vmap formulation this replaced). This is the
    kernel under both the fused multi-set analysis pass
    (``accuracy.analyze_sets_replicated``) and the fleet serving ``infer``
    path (``tm.predict_batch_replicated``).
    """
    R, cj, L = include.shape
    D, B, _ = literals.shape
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Lp, blk_l = _pad_l(L)
    cols = B + 1
    colsp = -(-cols // LANES) * LANES

    inc = jnp.zeros((R, cjp, Lp), dtype=jnp.int8).at[:, :cj, :L].set(
        include.astype(jnp.int8)
    )
    rhs = jnp.zeros((D, Lp, colsp), dtype=jnp.int8)
    rhs = rhs.at[:, :L, :B].set(
        jnp.swapaxes(1 - literals.astype(jnp.int8), 1, 2)
    )
    rhs = rhs.at[:, :L, B].set(1)

    out = pl.pallas_call(
        functools.partial(_kernel_replicated, 3),
        grid=(R, cjp // BLK_CJ, colsp // LANES, Lp // blk_l),
        in_specs=[
            pl.BlockSpec((1, BLK_CJ, blk_l), lambda r, i, j, l: (r, i, l)),
            pl.BlockSpec((1, blk_l, LANES), lambda r, i, j, l: (r % D, l, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, BLK_CJ, LANES), lambda r, i, j, l: (r, i, j)
        ),
        out_shape=jax.ShapeDtypeStruct((R, cjp, colsp), jnp.int32),
        interpret=interpret,
    )(inc, rhs)
    return out[:, :cj, :B], out[:, :cj, B]


def clause_eval_batch_replicated(
    include: jax.Array,   # [R, C, J, L] bool (post-fault TA actions)
    literals: jax.Array,  # [D, B, L] bool — replica r reads batch r % D
    *,
    training: bool,
    interpret: bool,
) -> jax.Array:
    """Kernel-backed replica-first batch clause outputs [R, B, C, J] bool.

    One launch of :func:`clause_counts_batch_replicated` — the whole
    analysis / serving-inference plane of R machines rides a single kernel
    grid with the ``r % D`` rhs index map doing the data-stream factoring
    (previously a per-replica gather + vmap of a single-machine kernel).
    Bit-identical to stacking ``ref.clause_eval_loop(include[r],
    literals[r % D])`` per replica.
    """
    R, C, J, L = include.shape
    B = literals.shape[1]
    viol, n_inc = clause_counts_batch_replicated(
        include.reshape(R, C * J, L), literals, interpret=interpret
    )
    fired = jnp.swapaxes(viol == 0, 1, 2).reshape(R, B, C, J)
    empty = (n_inc == 0).reshape(R, 1, C, J)
    return jnp.where(empty, jnp.bool_(training), fired)


# ---------------------------------------------------------------------------
# Bit-packed datapath: AND + popcount over uint32 words (DESIGN.md §13)
# ---------------------------------------------------------------------------
#
# The packed kernels are the closest TPU analogue of the FPGA's literal
# wires: the include bank and the literal rows are uint32 words (32 literals
# per lane element), and
#
#     violations[cj, b] = sum_w popcount(include[cj, w] & ~literal[b, w])
#
# This is VPU work, not MXU work — popcount has no matmul form — but the
# operand traffic shrinks 8x vs the int8 GEMM formulation and the word axis
# is 32x shorter than the literal axis, so the whole reduction usually fits
# ONE word block where the unpacked kernel streams several BLK_L blocks.
# The grid reuses the unpacked kernels' innermost-axis accumulation pattern
# on the word axis for datapaths wider than BLK_W*32 = 4096 literals.
#
# Tail safety: the packing contract (packing.py) zeroes include tail bits,
# so `include & ~literals` is zero at every pad position — the word padding
# added here (both word-axis padding to BLK_W and batch padding to BLK_B)
# only ever ANDs against zero include words and is sliced off the output.

BLK_B = 128   # datapoint columns per block (lane dim of the output tile)
BLK_W = 128   # uint32 words per block — 4096 literals per accumulation step


def _pad_w(W: int) -> tuple[int, int]:
    """(padded word width, word block) for a packed datapath of W words."""
    blk = min(BLK_W, -(-W // 8) * 8)  # 8 = uint32 sublane granule
    return -(-W // blk) * blk, blk


def _packed_kernel_replicated(w_axis: int, inc_ref, lit_ref, out_ref):
    # Leading length-1 replica block, as in _kernel_replicated.
    @pl.when(pl.program_id(w_axis) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    viol = inc_ref[0][:, None, :] & ~lit_ref[0][None, :, :]
    out_ref[...] += jnp.sum(
        jax.lax.population_count(viol).astype(jnp.int32), axis=-1
    )[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def clause_counts_batch_replicated_packed(
    include_packed: jax.Array,   # [R, CJ, W] uint32
    literals_packed: jax.Array,  # [D, B, W] uint32 — replica r reads r % D
    *,
    interpret: bool,
) -> jax.Array:
    """Violations [R, CJ, B] i32 in ONE launch: the packed replica plane.

    Grid (replica, clause-block, column-block, word-block) with the same
    ``r % D`` rhs index map as :func:`clause_counts_batch_replicated` — the
    factored data-stream rule carries over to packed words unchanged.
    """
    R, cj, W = include_packed.shape
    D, B, _ = literals_packed.shape
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Wp, blk_w = _pad_w(W)
    Bp = -(-B // BLK_B) * BLK_B

    inc = jnp.zeros((R, cjp, Wp), dtype=jnp.uint32).at[:, :cj, :W].set(
        include_packed
    )
    lit = jnp.zeros((D, Bp, Wp), dtype=jnp.uint32).at[:, :B, :W].set(
        literals_packed
    )

    out = pl.pallas_call(
        functools.partial(_packed_kernel_replicated, 3),
        grid=(R, cjp // BLK_CJ, Bp // BLK_B, Wp // blk_w),
        in_specs=[
            pl.BlockSpec((1, BLK_CJ, blk_w), lambda r, i, j, w: (r, i, w)),
            pl.BlockSpec((1, BLK_B, blk_w), lambda r, i, j, w: (r % D, j, w)),
        ],
        out_specs=pl.BlockSpec((1, BLK_CJ, BLK_B), lambda r, i, j, w: (r, i, j)),
        out_shape=jax.ShapeDtypeStruct((R, cjp, Bp), jnp.int32),
        interpret=interpret,
    )(inc, lit)
    return out[:, :cj, :B]


def clause_eval_batch_replicated_packed(
    include_packed: jax.Array,   # [R, C, J, W] uint32
    literals_packed: jax.Array,  # [D, B, W] uint32 — replica r reads r % D
    *,
    training: bool,
    interpret: bool,
) -> jax.Array:
    """Kernel-backed packed replica-first batch outputs [R, B, C, J] bool."""
    R, C, J, W = include_packed.shape
    B = literals_packed.shape[1]
    viol = clause_counts_batch_replicated_packed(
        include_packed.reshape(R, C * J, W), literals_packed,
        interpret=interpret,
    )
    fired = jnp.swapaxes(viol == 0, 1, 2).reshape(R, B, C, J)
    empty = ~jnp.any(include_packed != 0, axis=-1).reshape(R, 1, C, J)
    return jnp.where(empty, jnp.bool_(training), fired)


# ---------------------------------------------------------------------------
# Budgeted (pruned) eval: compacted include banks (DESIGN.md §16).
#
# The XLA-side ``ref.gather_include`` compacts the bank to the top-M ranked
# clauses per class BEFORE the pallas launch, so the kernel grid itself
# shrinks with the budget — C·M/BLK_CJ clause blocks instead of C·J/BLK_CJ —
# rather than masking pruned clauses inside a full-size contraction.
# ---------------------------------------------------------------------------


def clause_eval_batch_pruned_replicated(
    include: jax.Array, sel: jax.Array, literals: jax.Array,
    *, training: bool, interpret: bool,
) -> jax.Array:
    """[R, C, J, L] x sel [R, C, M] x [D, B, L] -> [R, B, C, M]."""
    return clause_eval_batch_replicated(
        _ref.gather_include(include, sel), literals,
        training=training, interpret=interpret,
    )


def clause_eval_batch_pruned_replicated_packed(
    include_packed: jax.Array, sel: jax.Array, literals_packed: jax.Array,
    *, training: bool, interpret: bool,
) -> jax.Array:
    """[R, C, J, W] u32 x sel [R, C, M] x [D, B, W] u32 -> [R, B, C, M]."""
    return clause_eval_batch_replicated_packed(
        _ref.gather_include(include_packed, sel), literals_packed,
        training=training, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Patch machines: the Convolutional TM (Granmo et al., arXiv:1905.09688).
#
# One MXU matmul per (replica, clause block, row) gives every patch's
# violation count for every clause of the block,
#
#     violations[p, cj] = sum_l (1 - literal[p, l]) * include[cj, l]
#
# with patches on sublanes and clauses on lanes, so the OR over patches and
# the patch choice are sublane reductions into lane-dense rows. The row
# after the last patch carries ones over the literals: its "violations" are
# the clause's include count (the emptiness test at inference). The patch
# choice needs the running count of firing patches, a second matmul against
# a lower-triangular ones matrix. Unlike the plain kernels this one is bound
# by compute: P x L x CJ multiply-adds per row.
# ---------------------------------------------------------------------------

BLK_PCJ = 1024   # clauses per block (lanes of the violation tile)


def _patch_pad(P: int, L: int, cj: int) -> tuple[int, int, int, int]:
    """(padded patches + count row, padded literals, clause block, padded
    clauses) of a patch evaluation."""
    Pp = -(-(P + 1) // 32) * 32
    Lp = -(-L // LANES) * LANES
    blk = min(BLK_PCJ, -(-cj // LANES) * LANES)
    return Pp, Lp, blk, -(-cj // blk) * blk


def _patch_kernel(n_patches: int, training: bool, choose: bool, *refs):
    # neg: [1, 1, Pp, Lp] int8 (1 - literal per patch; row P = ones),
    # inc: [1, Lp, blk] int8 -> out [1, 1, 1 + choose, blk] i32:
    # row 0 the clause output, row 1 the chosen patch.
    if choose:
        neg_ref, inc_ref, tri_ref, u_ref, out_ref = refs
    else:
        neg_ref, inc_ref, out_ref = refs
    v = jnp.dot(neg_ref[0, 0], inc_ref[0], preferred_element_type=jnp.int32)
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    fired = ((v == 0) & (row < n_patches)).astype(jnp.int32)   # [Pp, blk]
    hit = jnp.max(fired, axis=0, keepdims=True)                # [1, blk]
    if not training:
        n_inc = jnp.sum(jnp.where(row == n_patches, v, 0), axis=0,
                        keepdims=True)
        hit = jnp.where(n_inc == 0, 0, hit)
    out_ref[0, 0, 0:1, :] = hit
    if choose:
        cum = jnp.dot(tri_ref[...], fired.astype(jnp.int8),
                      preferred_element_type=jnp.int32)        # [Pp, blk]
        m = jnp.sum(fired, axis=0, keepdims=True)
        t = jnp.floor(u_ref[0, 0] * m.astype(jnp.float32)).astype(jnp.int32)
        t = jnp.minimum(t, m - 1)
        out_ref[0, 0, 1:2, :] = jnp.sum((cum <= t).astype(jnp.int32), axis=0,
                                        keepdims=True)


@functools.partial(jax.jit, static_argnames=("training", "interpret"))
def patch_clause_eval_replicated(
    include: jax.Array,    # [R, CJ, L] int8/bool — flattened (class, clause)
    literals: jax.Array,   # [D, B, P, L] bool — replica r reads batch r % D
    u: jax.Array | None,   # [D, B, CJ] f32 uniforms for the patch choice
    *,
    training: bool,
    interpret: bool,
):
    """(clause_out [R, B, CJ] bool, chosen [R, B, CJ] i32) in one launch,
    or clause_out alone without ``u``: the contract of
    ``ref.patch_clause_eval_replicated`` on flattened clauses.

    Grid (replica, clause block, row); the row axis is innermost, so a
    replica's include block stays in VMEM across its rows. The include
    bank is passed transposed ([R, L, CJ]) so that the matmul is the plain
    (patches x literals) @ (literals x clauses) form.
    """
    R, cj, L = include.shape
    D, B, P, _ = literals.shape
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    Pp, Lp, blk, cjp = _patch_pad(P, L, cj)
    choose = u is not None

    inc = jnp.zeros((R, Lp, cjp), dtype=jnp.int8).at[:, :L, :cj].set(
        jnp.swapaxes(include.astype(jnp.int8), 1, 2)
    )
    neg = jnp.zeros((D, B, Pp, Lp), dtype=jnp.int8)
    neg = neg.at[:, :, :P, :L].set(1 - literals.astype(jnp.int8))
    neg = neg.at[:, :, P, :L].set(1)
    operands = [neg, inc]
    in_specs = [
        pl.BlockSpec((1, 1, Pp, Lp), lambda r, i, b: (r % D, b, 0, 0)),
        pl.BlockSpec((1, Lp, blk), lambda r, i, b: (r, 0, i)),
    ]
    if choose:
        operands += [
            jnp.tril(jnp.ones((Pp, Pp), dtype=jnp.int8)),
            jnp.zeros((D, B, 1, cjp), dtype=jnp.float32).at[:, :, 0, :cj].set(
                u.astype(jnp.float32)),
        ]
        in_specs += [
            pl.BlockSpec((Pp, Pp), lambda r, i, b: (0, 0)),
            pl.BlockSpec((1, 1, 1, blk), lambda r, i, b: (r % D, b, 0, i)),
        ]
    nout = 2 if choose else 1
    out = pl.pallas_call(
        functools.partial(_patch_kernel, P, training, choose),
        grid=(R, cjp // blk, B),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, nout, blk),
                               lambda r, i, b: (r, b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((R, B, nout, cjp), jnp.int32),
        interpret=interpret,
        name="patch_clause_eval_replicated",
    )(*operands)
    hit = out[:, :, 0, :cj] != 0
    return (hit, out[:, :, 1, :cj]) if choose else hit
