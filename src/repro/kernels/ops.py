"""Public wrappers over the Pallas kernels.

Same contracts as :mod:`repro.kernels.ref` (the pure-jnp oracles) so the TM
core can switch backends with ``TMConfig.backend``. Every wrapper takes
``interpret``, which :func:`repro.kernels.dispatch.resolve` binds once from
the platform: compiled Mosaic kernels on a TPU (int8 32x128 blocks, 128-lane
last dims, MXU-shaped matmuls), the Pallas interpreter everywhere else —
the only way these kernels run off the chip.

On a device mesh (``jax.set_mesh``, which ``TMService(mesh=)`` and
``CrossValRun(mesh=)`` hold around their device work) every kernel runs per
device under ``shard_map``: Mosaic kernels cannot be partitioned by GSPMD.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import clause_eval as _ce
from repro.kernels import feedback as _fb

# The mesh axis that distributed.sharding.replica_shardings puts the
# replica axis on.
REPLICA_AXIS = "data"


def _shard_streams(x: jax.Array, local: int) -> jax.Array:
    """This shard's rows of a ``[D, ...]`` data stream: global replica
    ``off + r`` reads row ``(off + r) % D``, so the kernel's ``r % D`` rule
    holds again with D = ``local``."""
    off = jax.lax.axis_index(REPLICA_AXIS) * local
    return jnp.take(x, (off + jnp.arange(local)) % x.shape[0], axis=0)


def _per_device(kernel, *operands, replicas: int | None = None):
    """Run ``kernel(*operands)`` on each device of the context mesh.

    Without a mesh (or on one device) this is a plain call. On a mesh,
    operands with leading dim ``replicas`` split over :data:`REPLICA_AXIS`
    when it divides them, as ``replica_shardings`` places them; all other
    operands are replicated. A data stream of leading ``D < replicas`` keeps
    the ``r % D`` rule inside a shard when the shard's replica offset is a
    multiple of D, and is otherwise gathered to the shard's own rows.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return kernel(*operands)
    n = dict(mesh.shape).get(REPLICA_AXIS, 1)
    split = replicas is not None and n > 1 and replicas % n == 0
    local = replicas // n if split else 0

    def sharded(x) -> bool:
        return split and jnp.ndim(x) > 0 and x.shape[0] == replicas

    def body(*xs):
        xs = [
            x if sharded(o) or jnp.ndim(x) == 0 or local % x.shape[0] == 0
            else _shard_streams(x, local)
            for x, o in zip(xs, operands)
        ] if split else xs
        return kernel(*xs)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(P(REPLICA_AXIS) if sharded(x) else P()
                       for x in operands),
        out_specs=P(REPLICA_AXIS) if split else P(),
        check_vma=False,
    )(*operands)


def clause_eval_replicated(
    include: jax.Array, literals: jax.Array, *, training: bool,
    interpret: bool,
) -> jax.Array:
    """[R, C, J, L] x [D, L] -> [R, C, J] (see ref.clause_eval_replicated)."""
    return _per_device(
        partial(_ce.clause_eval_replicated, training=training,
                interpret=interpret),
        include, literals, replicas=include.shape[0],
    )


def clause_eval_batch_replicated(
    include: jax.Array, literals: jax.Array, *, training: bool,
    interpret: bool,
) -> jax.Array:
    """[R, C, J, L] x [D, B, L] -> [R, B, C, J] (see ref.clause_eval_batch_replicated)."""
    return _per_device(
        partial(_ce.clause_eval_batch_replicated, training=training,
                interpret=interpret),
        include, literals, replicas=include.shape[0],
    )


def clause_eval_batch_replicated_packed(
    include_packed: jax.Array, literals_packed: jax.Array, *, training: bool,
    interpret: bool,
) -> jax.Array:
    """[R, C, J, W] u32 x [D, B, W] u32 -> [R, B, C, J] bool (see
    ref.clause_eval_batch_replicated_packed)."""
    return _per_device(
        partial(_ce.clause_eval_batch_replicated_packed, training=training,
                interpret=interpret),
        include_packed, literals_packed, replicas=include_packed.shape[0],
    )


def clause_eval_batch_pruned_replicated(
    include: jax.Array, sel: jax.Array, literals: jax.Array, *,
    training: bool, interpret: bool,
) -> jax.Array:
    """[R, C, J, L] x sel [R, C, M] x [D, B, L] -> [R, B, C, M] (see
    ref.clause_eval_batch_pruned_replicated)."""
    return _per_device(
        partial(_ce.clause_eval_batch_pruned_replicated, training=training,
                interpret=interpret),
        include, sel, literals, replicas=include.shape[0],
    )


def clause_eval_batch_pruned_replicated_packed(
    include_packed: jax.Array, sel: jax.Array, literals_packed: jax.Array,
    *, training: bool, interpret: bool,
) -> jax.Array:
    """[R, C, J, W] u32 x sel [R, C, M] x [D, B, W] u32 -> [R, B, C, M]
    (see ref.clause_eval_batch_pruned_replicated_packed)."""
    return _per_device(
        partial(_ce.clause_eval_batch_pruned_replicated_packed,
                training=training, interpret=interpret),
        include_packed, sel, literals_packed,
        replicas=include_packed.shape[0],
    )


def feedback_step_replicated(
    ta_state: jax.Array,    # [R, C, J, L]
    literals: jax.Array,    # [D, L] — replica r reads row r % D
    clause_out: jax.Array,  # [R, C, J]
    type1_sel: jax.Array,   # [R, C, J]
    type2_sel: jax.Array,   # [R, C, J]
    u: jax.Array,           # [D, C, J, L] — replica r reads row r % D
    *,
    s: jax.Array,           # [R] f32 (scalars broadcast)
    n_states: int,
    s_policy: str,
    boost_true_positive: bool,
    interpret: bool,
) -> jax.Array:
    """Same contract as ref.feedback_step_replicated: R TA banks, ONE launch
    of the 2-D-grid (replica, clause-block) fused Pallas plane."""
    R, C, J, L = ta_state.shape
    D = literals.shape[0]
    s = jnp.broadcast_to(jnp.asarray(s, dtype=jnp.float32), (R,))
    p_strengthen = jnp.where(boost_true_positive, 1.0, (s - 1.0) / s)
    p_erase = (1.0 / s) if s_policy == "standard" else (s - 1.0) / s
    out = _per_device(
        partial(_fb.feedback_plane_replicated, n_states=n_states,
                interpret=interpret),
        ta_state.reshape(R, C * J, L),
        literals,
        clause_out.reshape(R, C * J),
        type1_sel.reshape(R, C * J),
        type2_sel.reshape(R, C * J),
        u.reshape(D, C * J, L),
        p_strengthen,
        jnp.asarray(p_erase, dtype=jnp.float32),
        replicas=R,
    )
    return out.reshape(R, C, J, L)


def patch_clause_eval_replicated(
    include: jax.Array, literals: jax.Array, u: jax.Array | None = None, *,
    training: bool, interpret: bool,
) -> tuple[jax.Array, jax.Array | None]:
    """[R, C, J, L] x [D, B, P, L] (x u [D, B, C, J]) -> (clause_out
    [R, B, C, J], chosen [R, B, C, J] | None) (see
    ref.patch_clause_eval_replicated)."""
    R, C, J, L = include.shape
    D, B = literals.shape[:2]
    kernel = partial(_ce.patch_clause_eval_replicated, training=training,
                     interpret=interpret)
    inc = include.reshape(R, C * J, L)
    if u is None:
        out = _per_device(lambda i, l: kernel(i, l, None), inc, literals,
                          replicas=R)
        return out.reshape(R, B, C, J), None
    out, chosen = _per_device(kernel, inc, literals,
                              u.reshape(D, B, C * J), replicas=R)
    return out.reshape(R, B, C, J), chosen.reshape(R, B, C, J)


def feedback_patch_replicated(
    ta_state: jax.Array,    # [R, C, J, L]
    literals: jax.Array,    # [R, C, J, L] — each clause's chosen patch
    clause_out: jax.Array,  # [R, C, J]
    type1_sel: jax.Array,   # [R, C, J]
    type2_sel: jax.Array,   # [R, C, J]
    u: jax.Array,           # [D, C, J, L] — replica r reads row r % D
    *,
    s: jax.Array,           # [R] f32 (scalars broadcast)
    n_states: int,
    s_policy: str,
    boost_true_positive: bool,
    interpret: bool,
) -> jax.Array:
    """Same contract as ref.feedback_patch_replicated: R TA banks, ONE
    launch of the fused Pallas plane with a literal row per clause."""
    R, C, J, L = ta_state.shape
    D = u.shape[0]
    s = jnp.broadcast_to(jnp.asarray(s, dtype=jnp.float32), (R,))
    p_strengthen = jnp.where(boost_true_positive, 1.0, (s - 1.0) / s)
    p_erase = (1.0 / s) if s_policy == "standard" else (s - 1.0) / s
    out = _per_device(
        partial(_fb.feedback_patch_replicated, n_states=n_states,
                interpret=interpret),
        ta_state.reshape(R, C * J, L),
        literals.reshape(R, C * J, L),
        clause_out.reshape(R, C * J),
        type1_sel.reshape(R, C * J),
        type2_sel.reshape(R, C * J),
        u.reshape(D, C * J, L),
        p_strengthen,
        jnp.asarray(p_erase, dtype=jnp.float32),
        replicas=R,
    )
    return out.reshape(R, C, J, L)
