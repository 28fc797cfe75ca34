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


def _kernel(l_axis: int, inc_ref, rhs_ref, out_ref):
    # inc: [BLK_CJ, blk_l] int8, rhs: [blk_l, LANES] int8 -> accumulate
    # [BLK_CJ, LANES] i32 partial sums over the innermost (literal) axis.
    @pl.when(pl.program_id(l_axis) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        inc_ref[...], rhs_ref[...], preferred_element_type=jnp.int32
    )


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
def clause_counts(
    include: jax.Array,   # [CJ, L] int8/bool — flattened (class, clause) rows
    literals: jax.Array,  # [L] bool
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(violations [CJ] i32, n_included [CJ] i32) via one MXU matmul."""
    cj, L = include.shape
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Lp, blk_l = _pad_l(L)

    inc = jnp.zeros((cjp, Lp), dtype=jnp.int8).at[:cj, :L].set(
        include.astype(jnp.int8)
    )
    # rhs col 0: ~literal (violation counter); col 1: ones (include counter).
    rhs = jnp.zeros((Lp, LANES), dtype=jnp.int8)
    rhs = rhs.at[:L, 0].set(1 - literals.astype(jnp.int8))
    rhs = rhs.at[:L, 1].set(1)

    out = pl.pallas_call(
        functools.partial(_kernel, 1),
        grid=(cjp // BLK_CJ, Lp // blk_l),
        in_specs=[
            pl.BlockSpec((BLK_CJ, blk_l), lambda i, l: (i, l)),
            pl.BlockSpec((blk_l, LANES), lambda i, l: (l, 0)),
        ],
        out_specs=pl.BlockSpec((BLK_CJ, LANES), lambda i, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cjp, LANES), jnp.int32),
        interpret=interpret,
    )(inc, rhs)
    return out[:cj, 0], out[:cj, 1]


def clause_eval(
    include: jax.Array,   # [C, J, L] bool (post-fault TA actions)
    literals: jax.Array,  # [L] bool
    *,
    training: bool,
    interpret: bool,
) -> jax.Array:
    """Kernel-backed clause outputs [C, J] bool (same contract as ref)."""
    C, J, L = include.shape
    viol, n_inc = clause_counts(
        include.reshape(C * J, L), literals, interpret=interpret
    )
    fired = viol == 0
    empty = n_inc == 0
    out = jnp.where(empty, jnp.bool_(training), fired)
    return out.reshape(C, J)


@functools.partial(jax.jit, static_argnames=("interpret",))
def clause_counts_batch(
    include: jax.Array,   # [CJ, L] int8/bool — flattened (class, clause) rows
    literals: jax.Array,  # [B, L] bool — one row per datapoint
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(violations [CJ, B] i32, n_included [CJ] i32) via ONE MXU matmul.

    The batch-first form of :func:`clause_counts`: rhs columns 0..B-1 carry
    ``~literal_b`` (per-datapoint violation counters) and column B carries
    ones (the include counter — datapoint-independent, so a single column
    refines the [L, 2B] design down to [L, B+1]). The include bank streams
    HBM->VMEM once per *batch*; the grid tiles the flattened
    (class x clause) axis, the datapoint-column axis and the literal axis
    (innermost, accumulated).
    """
    cj, L = include.shape
    B = literals.shape[0]
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Lp, blk_l = _pad_l(L)
    cols = B + 1
    colsp = -(-cols // LANES) * LANES

    inc = jnp.zeros((cjp, Lp), dtype=jnp.int8).at[:cj, :L].set(
        include.astype(jnp.int8)
    )
    rhs = jnp.zeros((Lp, colsp), dtype=jnp.int8)
    rhs = rhs.at[:L, :B].set((1 - literals.astype(jnp.int8)).T)
    rhs = rhs.at[:L, B].set(1)

    out = pl.pallas_call(
        functools.partial(_kernel, 2),
        grid=(cjp // BLK_CJ, colsp // LANES, Lp // blk_l),
        in_specs=[
            pl.BlockSpec((BLK_CJ, blk_l), lambda i, j, l: (i, l)),
            pl.BlockSpec((blk_l, LANES), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((BLK_CJ, LANES), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((cjp, colsp), jnp.int32),
        interpret=interpret,
    )(inc, rhs)
    return out[:cj, :B], out[:cj, B]


def clause_eval_batch(
    include: jax.Array,   # [C, J, L] bool (post-fault TA actions)
    literals: jax.Array,  # [B, L] bool
    *,
    training: bool,
    interpret: bool,
) -> jax.Array:
    """Kernel-backed batch-first clause outputs [B, C, J] bool."""
    C, J, L = include.shape
    B = literals.shape[0]
    viol, n_inc = clause_counts_batch(
        include.reshape(C * J, L), literals, interpret=interpret
    )
    fired = (viol == 0).T.reshape(B, C, J)
    empty = (n_inc == 0).reshape(C, J)
    return jnp.where(empty[None], jnp.bool_(training), fired)


@functools.partial(jax.jit, static_argnames=("interpret",))
def clause_counts_replicated(
    include: jax.Array,   # [R, CJ, L] int8/bool — per-replica include banks
    literals: jax.Array,  # [D, L] bool — replica r reads row r % D
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """(violations [R, CJ] i32, n_included [R, CJ] i32) in ONE kernel launch.

    Replica-first form of :func:`clause_counts`: a grid over
    (replica, clause-block, literal-block), each replica contracting its own
    include bank against its data stream's literal row. The rhs BlockSpec
    maps replica ``r`` to literal row ``r % D``, so a hyperparameter grid
    sharing one ordering's data stream stores the rhs once per ordering.
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
    rhs = jnp.zeros((D, Lp, LANES), dtype=jnp.int8)
    rhs = rhs.at[:, :L, 0].set(1 - literals.astype(jnp.int8))
    rhs = rhs.at[:, :L, 1].set(1)

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

    The replica-first form of :func:`clause_counts_batch`: a 4-D grid over
    (replica, clause-block, column-block, literal-block), each replica
    contracting its own include bank against its data stream's [L, B+1]
    rhs. The rhs BlockSpec maps replica ``r`` to stream ``r % D`` — the
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
    (previously a per-replica gather + vmap of :func:`clause_eval_batch`).
    Bit-identical to stacking ``clause_eval_batch(include[r],
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


def _packed_kernel(w_axis: int, inc_ref, lit_ref, out_ref):
    # inc: [BLK_CJ, blk_w] u32, lit: [BLK_B, blk_w] u32 -> accumulate
    # [BLK_CJ, BLK_B] i32 violation partial sums over the word axis.
    @pl.when(pl.program_id(w_axis) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    viol = inc_ref[...][:, None, :] & ~lit_ref[...][None, :, :]
    out_ref[...] += jnp.sum(
        jax.lax.population_count(viol).astype(jnp.int32), axis=-1
    )


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
def clause_counts_batch_packed(
    include_packed: jax.Array,   # [CJ, W] uint32 — packed include rows
    literals_packed: jax.Array,  # [B, W] uint32 — packed literal rows
    *,
    interpret: bool,
) -> jax.Array:
    """Violations [CJ, B] i32 via the word-tiled AND+popcount grid.

    ``n_included`` has no per-datapoint dependence, so unlike the unpacked
    kernels there is no ones-column trick to fold it into the same launch —
    callers derive emptiness from the include words directly (cheap:
    [CJ, W] is 32x smaller than the bool include bank).
    """
    cj, W = include_packed.shape
    B = literals_packed.shape[0]
    cjp = -(-cj // BLK_CJ) * BLK_CJ
    Wp, blk_w = _pad_w(W)
    Bp = -(-B // BLK_B) * BLK_B

    inc = jnp.zeros((cjp, Wp), dtype=jnp.uint32).at[:cj, :W].set(
        include_packed
    )
    lit = jnp.zeros((Bp, Wp), dtype=jnp.uint32).at[:B, :W].set(
        literals_packed
    )

    out = pl.pallas_call(
        functools.partial(_packed_kernel, 2),
        grid=(cjp // BLK_CJ, Bp // BLK_B, Wp // blk_w),
        in_specs=[
            pl.BlockSpec((BLK_CJ, blk_w), lambda i, j, w: (i, w)),
            pl.BlockSpec((BLK_B, blk_w), lambda i, j, w: (j, w)),
        ],
        out_specs=pl.BlockSpec((BLK_CJ, BLK_B), lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((cjp, Bp), jnp.int32),
        interpret=interpret,
    )(inc, lit)
    return out[:cj, :B]


def clause_eval_batch_packed(
    include_packed: jax.Array,   # [C, J, W] uint32 (packed post-fault actions)
    literals_packed: jax.Array,  # [B, W] uint32
    *,
    training: bool,
    interpret: bool,
) -> jax.Array:
    """Kernel-backed packed batch clause outputs [B, C, J] bool.

    Same contract as ``ref.clause_eval_batch_packed`` — and, through the
    packing contract, bit-identical to the unpacked oracle.
    """
    C, J, W = include_packed.shape
    B = literals_packed.shape[0]
    viol = clause_counts_batch_packed(
        include_packed.reshape(C * J, W), literals_packed, interpret=interpret
    )
    fired = (viol == 0).T.reshape(B, C, J)
    empty = ~jnp.any(include_packed != 0, axis=-1)
    return jnp.where(empty[None], jnp.bool_(training), fired)


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


def clause_eval_batch_pruned(
    include: jax.Array, sel: jax.Array, literals: jax.Array,
    *, training: bool, interpret: bool,
) -> jax.Array:
    """[C, J, L] x sel [C, M] x [B, L] -> [B, C, M] (see ref twin)."""
    return clause_eval_batch(
        _ref.gather_include(include, sel), literals,
        training=training, interpret=interpret,
    )


def clause_eval_batch_pruned_replicated(
    include: jax.Array, sel: jax.Array, literals: jax.Array,
    *, training: bool, interpret: bool,
) -> jax.Array:
    """[R, C, J, L] x sel [R, C, M] x [D, B, L] -> [R, B, C, M]."""
    return clause_eval_batch_replicated(
        _ref.gather_include(include, sel), literals,
        training=training, interpret=interpret,
    )


def clause_eval_batch_pruned_packed(
    include_packed: jax.Array, sel: jax.Array, literals_packed: jax.Array,
    *, training: bool, interpret: bool,
) -> jax.Array:
    """[C, J, W] u32 x sel [C, M] x [B, W] u32 -> [B, C, M]."""
    return clause_eval_batch_packed(
        _ref.gather_include(include_packed, sel), literals_packed,
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
