"""Pure-jnp oracles for the Pallas kernels.

These are the semantic ground truth: the Pallas kernels in ``clause_eval.py``
and ``feedback.py`` are asserted allclose against these across shape/dtype
sweeps (see tests/test_kernels.py). They are also the default CPU backend.

Every backend entry is replica-first; one machine is its R = 1 slice. The
per-machine functions :func:`clause_eval`, :func:`clause_eval_loop` and
:func:`feedback_step` are no backend entries: they define one machine, and
the replicated entries are tested against them stacked per replica.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def clause_eval(include: jax.Array, literals: jax.Array, *, training: bool) -> jax.Array:
    """Clause outputs: AND over included literals.

    Args:
      include: [C, J, L] bool — post-fault TA actions (L = 2*features).
      literals: [L] bool — input literal vector [x, ~x].
      training: empty clauses output 1 while training, 0 at inference.

    Returns: [C, J] bool clause outputs.
    """
    # A clause fails iff some included literal is 0.
    match = jnp.logical_or(~include, literals[None, None, :])
    fired = jnp.all(match, axis=-1)
    empty = ~jnp.any(include, axis=-1)
    return jnp.where(empty, jnp.bool_(training), fired)


def clause_eval_loop(
    include: jax.Array, literals: jax.Array, *, training: bool
) -> jax.Array:
    """Per-sample-loop batched eval: the oracle the batch paths are tested
    against (literally a vmap of :func:`clause_eval` over rows)."""
    return jax.vmap(lambda l: clause_eval(include, l, training=training))(literals)


def clause_eval_replicated(
    include: jax.Array, literals: jax.Array, *, training: bool
) -> jax.Array:
    """Replica-first clause eval: include [R, C, J, L] x literals [D, L] ->
    [R, C, J].

    Replica ``r`` evaluates against literal row ``r % D`` (``D`` must divide
    ``R``). The cross-validation engine lays replicas out grid-major /
    ordering-minor — replicas that share a data stream (one ordering trained
    under many (s, T) cells) are adjacent modulo ``D``, so the literal bank is
    stored once per *ordering* and broadcast across the hyperparameter grid
    instead of being tiled ``R/D``-fold. MUST equal stacking
    :func:`clause_eval` with ``(include[r], literals[r % D])`` bit-for-bit.
    """
    R, C, J, L = include.shape
    D = literals.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    inc = include.reshape(R // D, D, C, J, L)
    lit = literals[None, :, None, None, :]
    fired = jnp.all(jnp.logical_or(~inc, lit), axis=-1)
    empty = ~jnp.any(inc, axis=-1)
    return jnp.where(empty, jnp.bool_(training), fired).reshape(R, C, J)


def clause_eval_batch_replicated(
    include: jax.Array, literals: jax.Array, *, training: bool
) -> jax.Array:
    """Replica-first batch eval: include [R, C, J, L] x literals [D, B, L] ->
    [R, B, C, J].

    One batched GEMM over all replicas (replica ``r`` reads literal batch
    ``r % D``): the whole cross-validation sweep's accuracy analysis — all
    three per-cycle sets concatenated (``accuracy.analyze_sets_replicated``)
    — and the serving fleet's batched ``infer`` path are each a single
    contraction on this entry. The include bank is the stationary GEMM
    operand, read once per *batch*:

        violations[b, cj] = sum_l (1 - literal[b, l]) * include[cj, l]
        clause fires     <=> violations == 0
        clause is empty  <=> n_included == 0

    Violation counts are integers << 2^24, so f32 accumulation is exact and
    the result is bit-identical to stacking :func:`clause_eval_loop` per
    replica.
    """
    R, C, J, L = include.shape
    D, B, _ = literals.shape
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    inc = include.reshape(R // D, D, C * J, L).astype(jnp.float32)
    neg = 1.0 - literals.astype(jnp.float32)                  # [D, B, L]
    viol = jnp.einsum("hdkl,dbl->hdbk", inc, neg)
    fired = (viol == 0).reshape(R // D, D, B, C, J)
    empty = ~jnp.any(include, axis=-1).reshape(R // D, D, 1, C, J)
    out = jnp.where(empty, jnp.bool_(training), fired)
    return out.reshape(R, B, C, J)


def clause_eval_batch_replicated_packed(
    include_packed: jax.Array, literals_packed: jax.Array, *, training: bool
) -> jax.Array:
    """Replica-first bit-packed batch eval — the FPGA's AND-tree,
    word-at-a-time: include [R, C, J, W] uint32 (packed per
    ``packing.pack_include``, W = 2*ceil(f/32) words, tail bits ZERO) x
    literals [D, B, W] uint32 (``packing.pack_literals``) -> [R, B, C, J]
    bool.

        violations[b, c, j] = sum_w popcount(include[c,j,w] & ~literal[b,w])
        clause fires      <=> violations == 0
        clause is empty   <=> sum_w popcount(include[c,j,w]) == 0

    Replica ``r`` reads literal batch ``r % D`` — the same factored layout
    rule as :func:`clause_eval_batch_replicated`, on packed words, and MUST
    be bit-identical to it on the unpacked operands. Tail safety: include
    tail bits are zero by the packing contract, so ``include & ~literals``
    is zero at every pad position even though the complement sets the
    literal tail to ones — each per-word popcount equals the unpacked
    per-word violation count exactly.
    """
    R, C, J, W = include_packed.shape
    D, B, _ = literals_packed.shape
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    inc = include_packed.reshape(R // D, D, 1, C, J, W)
    lit = literals_packed[None, :, :, None, None, :]      # [1, D, B, 1, 1, W]
    viol = jnp.sum(
        jax.lax.population_count(inc & ~lit).astype(jnp.int32), axis=-1
    )                                                     # [H, D, B, C, J]
    fired = viol == 0
    empty = ~jnp.any(include_packed != 0, axis=-1)        # [R, C, J]
    empty = empty.reshape(R // D, D, 1, C, J)
    out = jnp.where(empty, jnp.bool_(training), fired)
    return out.reshape(R, B, C, J)


def gather_include(include: jax.Array, sel: jax.Array) -> jax.Array:
    """Compact an include bank to the selected clauses: [..., C, J, L|W] x
    sel [..., C, M] i32 -> [..., C, M, L|W].

    The budgeted-serve primitive (DESIGN.md §16): instead of masking
    pruned clauses out (which still pays the full [C·J, L] contraction),
    the include bank is *gathered* down to the top-M ranked clauses per
    class, so the clause contraction — GEMM rows on ref, grid blocks on
    pallas — shrinks with the budget. Works on unpacked [.., L] bool and
    packed [.., W] uint32 banks alike (the gather never touches the last
    axis, so the §13 packing contract — tail bits zero — is preserved).
    """
    return jnp.take_along_axis(include, sel[..., None], axis=-2)


def clause_eval_batch_pruned_replicated(
    include: jax.Array, sel: jax.Array, literals: jax.Array, *, training: bool
) -> jax.Array:
    """Replica-first budgeted eval: include [R, C, J, L] x sel [R, C, M] x
    literals [D, B, L] -> [R, B, C, M] (replica ``r`` reads batch
    ``r % D`` and its OWN clause ranking ``sel[r]``)."""
    return clause_eval_batch_replicated(
        gather_include(include, sel), literals, training=training
    )


def clause_eval_batch_pruned_replicated_packed(
    include_packed: jax.Array, sel: jax.Array, literals_packed: jax.Array,
    *, training: bool,
) -> jax.Array:
    """Replica-first bit-packed budgeted eval: [R, C, J, W] u32 x
    [R, C, M] x [D, B, W] u32 -> [R, B, C, M]."""
    return clause_eval_batch_replicated_packed(
        gather_include(include_packed, sel), literals_packed,
        training=training,
    )


def feedback_step(
    ta_state: jax.Array,    # [C, J, L] int8/int16 (pre-update)
    literals: jax.Array,    # [L] bool
    clause_out: jax.Array,  # [C, J] bool (training-mode, post-fault outputs)
    type1_sel: jax.Array,   # [C, J] bool — clauses given Type I feedback
    type2_sel: jax.Array,   # [C, J] bool — clauses given Type II feedback
    u: jax.Array,           # [C, J, L] f32 uniforms in [0,1) — one draw per TA
    *,
    s: jax.Array,           # scalar f32
    n_states: int,
    s_policy: str,
    boost_true_positive: bool,
) -> jax.Array:
    """One datapoint's TA-bank update (Type I + Type II). Returns new ta_state.

    Type I (recognize/erase — combats false negatives):
      clause=1 & lit=1:  strengthen include  w.p. p_strengthen
      otherwise:         push toward exclude w.p. p_erase
    Type II (reject — combats false positives):
      clause=1 & lit=0 & excluded: +1 toward include, deterministic.

    s-policies (DESIGN.md §2):
      standard: p_strengthen=(s-1)/s (or 1 if boost), p_erase=1/s
      hardware: p_strengthen=(s-1)/s (or 1 if boost), p_erase=(s-1)/s
                (all stochastic events rarer as s->1: the paper's low-power bias)
    """
    p_strengthen = jnp.where(boost_true_positive, 1.0, (s - 1.0) / s)
    p_erase = (1.0 / s) if s_policy == "standard" else (s - 1.0) / s

    lit = literals[None, None, :]
    c_out = clause_out[:, :, None]
    include = ta_state > n_states

    # Type I deltas.
    strengthen = c_out & lit
    d1 = jnp.where(
        strengthen,
        (u < p_strengthen).astype(jnp.int32),
        -((u < p_erase).astype(jnp.int32)),
    )

    # Type II deltas: insert a blocking literal.
    d2 = (c_out & ~lit & ~include).astype(jnp.int32)

    delta = (
        type1_sel[:, :, None].astype(jnp.int32) * d1
        + type2_sel[:, :, None].astype(jnp.int32) * d2
    )
    new_state = jnp.clip(ta_state.astype(jnp.int32) + delta, 1, 2 * n_states)
    return new_state.astype(ta_state.dtype)


def feedback_step_replicated(
    ta_state: jax.Array,    # [R, C, J, L] int8/int16 (pre-update)
    literals: jax.Array,    # [D, L] bool — replica r reads row r % D
    clause_out: jax.Array,  # [R, C, J] bool
    type1_sel: jax.Array,   # [R, C, J] bool
    type2_sel: jax.Array,   # [R, C, J] bool
    u: jax.Array,           # [D, C, J, L] f32 — replica r reads row r % D
    *,
    s: jax.Array,           # [R] f32 (scalars broadcast)
    n_states: int,
    s_policy: str,
    boost_true_positive: bool,
) -> jax.Array:
    """R independent TA banks updated as ONE fused elementwise plane.

    This is the training half of the replica-parallel engine: every
    (ordering x s x T) replica of a cross-validation sweep advances one
    datapoint in a single [R, C·J, L] update instead of R separate
    :func:`feedback_step` planes. Two things make it faster than a vmap of
    the per-replica oracle without changing a single bit of the result:

    * the uniforms (and literals) are *factored*: replicas sharing a data
      stream (same ordering, different (s, T)) consume the same draws, so
      ``u`` is stored once per data replica and broadcast across the grid
      rather than tiled to [R, C, J, L];
    * the delta arithmetic runs at the TA bank's native int8 width. Exact:
      states are <= 2N <= 126 in int8, Type II applies only to excluded TAs
      (state <= N), so ``state + delta`` never exceeds 127.

    MUST be bit-identical to stacking ``feedback_step(ta[r], literals[r % D],
    ..., u[r % D], s=s[r])`` over replicas — asserted in tests/test_kernels.py.
    """
    R, C, J, L = ta_state.shape
    D = literals.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    H = R // D

    s = jnp.broadcast_to(jnp.asarray(s, jnp.float32), (R,)).reshape(H, D, 1, 1, 1)
    p_strengthen = jnp.where(boost_true_positive, 1.0, (s - 1.0) / s)
    p_erase = (1.0 / s) if s_policy == "standard" else (s - 1.0) / s

    return _update_replicated(
        ta_state, literals[None, :, None, None, :], clause_out, type1_sel,
        type2_sel, u, p_strengthen, p_erase, H=H, n_states=n_states,
    )


def _update_replicated(ta_state, lit, clause_out, type1_sel, type2_sel, u,
                       p_strengthen, p_erase, *, H: int, n_states: int):
    """The fused Type I/II plane of R = H x D banks; ``lit`` broadcasts
    against ``[H, D, C, J, L]`` (one literal row per data stream, or one
    per clause)."""
    R, C, J, L = ta_state.shape
    D = R // H
    ta = ta_state.reshape(H, D, C, J, L)
    uB = u[None]
    c_out = clause_out.reshape(H, D, C, J)[..., None]
    t1 = type1_sel.reshape(H, D, C, J)[..., None]
    t2 = type2_sel.reshape(H, D, C, J)[..., None]
    include = ta > n_states

    # int8 states: all arithmetic stays int8 (exact — see docstring); wider
    # states fall back to the oracle's int32 maths.
    acc_dtype = jnp.int8 if ta_state.dtype == jnp.int8 else jnp.int32

    strengthen = c_out & lit
    d1 = jnp.where(
        strengthen,
        (uB < p_strengthen).astype(acc_dtype),
        -((uB < p_erase).astype(acc_dtype)),
    )
    d2 = (c_out & ~lit & ~include).astype(acc_dtype)
    delta = jnp.where(t1, d1, 0) + jnp.where(t2, d2, 0)
    new_state = jnp.clip(ta.astype(acc_dtype) + delta, 1, 2 * n_states)
    return new_state.reshape(R, C, J, L).astype(ta_state.dtype)


# ---------------------------------------------------------------------------
# Patch machines: the Convolutional TM (Granmo et al., arXiv:1905.09688)
# ---------------------------------------------------------------------------


def patch_clause_eval_replicated(
    include: jax.Array,        # [R, C, J, L] bool (post-fault TA actions)
    literals: jax.Array,       # [D, B, P, L] bool — replica r reads batch r % D
    u: jax.Array | None = None,  # [D, B, C, J] f32 — one uniform per clause
    *,
    training: bool,
) -> tuple[jax.Array, jax.Array | None]:
    """Patch clause eval: (clause_out [R, B, C, J] bool, chosen [R, B, C, J]
    i32, or None without ``u``).

    A clause's conjunction on patch p follows the plain rule: it fires iff
    every included literal of p is 1. The clause output is the OR over the
    P patches. An empty clause fires on every patch while training and is
    silent at inference.

    With ``u``: m is the number of patches the clause fired on, and
    ``chosen`` is the ``min(floor(u * m), m - 1)``-th of them in patch
    order (counting from 0): the patch whose literals take the clause's
    feedback. It is 0 when m = 0, where no patch is used.

        violations[p, cj] = sum_l (1 - literal[p, l]) * include[cj, l]

    Counts are integers <= L << 2^24, so the f32 contraction is exact.
    """
    R, C, J, L = include.shape
    D, B, P, _ = literals.shape
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    H, CJ = R // D, C * J
    inc = include.reshape(H, D, CJ, L).astype(jnp.float32)
    neg = 1.0 - literals.astype(jnp.float32)              # [D, B, P, L]
    viol = jnp.einsum("dbpl,hdkl->hdbpk", neg, inc)       # [H, D, B, P, CJ]
    fired = viol == 0
    out = jnp.any(fired, axis=-2)                         # [H, D, B, CJ]
    if not training:
        empty = ~jnp.any(include, axis=-1).reshape(H, D, 1, CJ)
        out = out & ~empty
    out = out.reshape(R, B, C, J)
    if u is None:
        return out, None
    m = jnp.sum(fired, axis=-2, dtype=jnp.int32)          # [H, D, B, CJ]
    t = jnp.floor(u.reshape(1, D, B, CJ) * m.astype(jnp.float32))
    t = jnp.minimum(t.astype(jnp.int32), m - 1)
    cum = jnp.cumsum(fired, axis=-2, dtype=jnp.int32)     # inclusive counts
    chosen = jnp.sum(cum <= t[..., None, :], axis=-2, dtype=jnp.int32)
    return out, chosen.reshape(R, B, C, J)


def feedback_patch_replicated(
    ta_state: jax.Array,    # [R, C, J, L] int8/int16 (pre-update)
    literals: jax.Array,    # [R, C, J, L] bool — each clause's chosen patch
    clause_out: jax.Array,  # [R, C, J] bool
    type1_sel: jax.Array,   # [R, C, J] bool
    type2_sel: jax.Array,   # [R, C, J] bool
    u: jax.Array,           # [D, C, J, L] f32 — replica r reads row r % D
    *,
    s: jax.Array,           # [R] f32 (scalars broadcast)
    n_states: int,
    s_policy: str,
    boost_true_positive: bool,
) -> jax.Array:
    """:func:`feedback_step_replicated` with one literal row per clause:
    a patch machine's clause takes feedback from the literals of the patch
    it chose (:func:`patch_clause_eval_replicated`). MUST equal that entry
    on banks whose clauses all chose literal rows equal to the row given."""
    R, C, J, L = ta_state.shape
    D = u.shape[0]
    if R % D:
        raise ValueError(f"data replicas {D} must divide replicas {R}")
    H = R // D
    s = jnp.broadcast_to(jnp.asarray(s, jnp.float32), (R,)).reshape(H, D, 1, 1, 1)
    p_strengthen = jnp.where(boost_true_positive, 1.0, (s - 1.0) / s)
    p_erase = (1.0 / s) if s_policy == "standard" else (s - 1.0) / s
    return _update_replicated(
        ta_state, literals.reshape(H, D, C, J, L), clause_out, type1_sel,
        type2_sel, u, p_strengthen, p_erase, H=H, n_states=n_states,
    )
