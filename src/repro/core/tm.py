"""Tsetlin Machine core — the paper's central datapath, in JAX.

The TM here mirrors the FPGA architecture of the paper:

* a bank of Tsetlin automata (TA) per (class, clause, literal) whose 2N-state
  counters decide include/exclude of each literal,
* clause evaluation as an include-masked AND over literals (+ complements),
* a majority vote (positive/negative polarity clauses) per class,
* **over-provisioning**: the arrays are allocated at `max_classes`/`max_clauses`
  (the paper's pre-synthesis parameters) while *runtime masks* select the active
  subset — the JAX analogue of avoiding FPGA re-synthesis is avoiding re-JIT:
  shapes never change when classes/clauses are enabled at runtime,
* **fault injection**: per-TA AND/OR masks force TA action outputs to stuck-at
  values exactly as the paper's fault controller does (§3.1.2).

Everything is a pure function over explicit state so the whole machine can be
`vmap`-ed over cross-validation orderings / hyperparameter grids and `pjit`-ed
over a device mesh (the paper's goal (ii): accelerated CV + HP search).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, packing


# ---------------------------------------------------------------------------
# Configuration (the paper's design-time parameters, §3.1)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TMConfig:
    """Design-time parameters — fixed at trace time (≈ FPGA synthesis time).

    `max_classes` / `max_clauses` over-provision resources (§3.1.1); the active
    subset is selected at *runtime* via masks carried in :class:`TMRuntime`.
    """

    n_features: int                  # booleanized input width (iris: 16)
    max_classes: int                 # provisioned classes (≥ active classes)
    max_clauses: int                 # provisioned clauses per class (even)
    n_states: int = 99               # N states per action (TA has 2N states)
    s_policy: str = "standard"       # "standard" | "hardware"  (see DESIGN.md §2)
    boost_true_positive: bool = True # deterministic strengthen on (clause=1,lit=1)
    backend: str = "auto"            # kernel backend name (see kernels/dispatch.py)

    def __post_init__(self):
        if self.max_clauses % 2:
            raise ValueError("max_clauses must be even (half +, half - polarity)")
        if self.n_states < 1:
            raise ValueError("n_states must be >= 1")
        if self.s_policy not in ("standard", "hardware"):
            raise ValueError(f"unknown s_policy {self.s_policy!r}")
        if self.backend not in dispatch.available():
            raise ValueError(
                f"unknown backend {self.backend!r}; "
                f"available: {dispatch.available()}"
            )

    @property
    def n_literals(self) -> int:
        return 2 * self.n_features

    @property
    def state_dtype(self):
        # 2N must fit the dtype; int8 keeps the TA bank tiny (paper: few bits/TA).
        return jnp.int8 if 2 * self.n_states <= 127 else jnp.int16


# ---------------------------------------------------------------------------
# Runtime-controllable knobs (the paper's I/O-port parameters, §3.1)
# ---------------------------------------------------------------------------


class TMRuntime(NamedTuple):
    """Runtime ports: adjustable WITHOUT re-JIT (paper: without re-synthesis).

    * ``s``/``T`` — the runtime hyperparameter ports,
    * ``clause_mask`` — the clause-number port (over-provisioned clauses gated),
    * ``class_mask`` — over-provisioned classes gated until introduced,
    * ``ta_and_mask``/``ta_or_mask`` — the fault-controller mappings (§3.1.2):
      action' = (action AND and_mask) OR or_mask. Fault-free: and=1, or=0.
    """

    s: jax.Array            # scalar f32 — sensitivity
    T: jax.Array            # scalar i32 — vote threshold/target
    clause_mask: jax.Array  # [max_clauses] bool
    class_mask: jax.Array   # [max_classes] bool
    ta_and_mask: jax.Array  # [max_classes, max_clauses, 2f] bool
    ta_or_mask: jax.Array   # [max_classes, max_clauses, 2f] bool


class TMState(NamedTuple):
    """Learnt state: the TA bank. States 1..N => exclude, N+1..2N => include."""

    ta_state: jax.Array  # [max_classes, max_clauses, 2f] int8/int16


def init_state(cfg: TMConfig, key: Optional[jax.Array] = None) -> TMState:
    """TA bank initialised at the decision boundary (states N or N+1).

    The FPGA initialises automata randomly on either side of the boundary;
    with a key we do the same, without a key we use the deterministic N
    (all-exclude) start which the hardware also supports.
    """
    shape = (cfg.max_classes, cfg.max_clauses, cfg.n_literals)
    n = cfg.n_states
    if key is None:
        ta = jnp.full(shape, n, dtype=cfg.state_dtype)
    else:
        coin = jax.random.bernoulli(key, 0.5, shape)
        ta = jnp.where(coin, n + 1, n).astype(cfg.state_dtype)
    return TMState(ta_state=ta)


def init_runtime(
    cfg: TMConfig,
    *,
    s: float = 3.9,
    T: int = 15,
    n_active_classes: Optional[int] = None,
    n_active_clauses: Optional[int] = None,
) -> TMRuntime:
    """Fault-free runtime with the first ``n_active_*`` resources enabled."""
    n_cls = cfg.max_classes if n_active_classes is None else n_active_classes
    n_clz = cfg.max_clauses if n_active_clauses is None else n_active_clauses
    shape = (cfg.max_classes, cfg.max_clauses, cfg.n_literals)
    return TMRuntime(
        s=jnp.float32(s),
        T=jnp.int32(T),
        clause_mask=jnp.arange(cfg.max_clauses) < n_clz,
        class_mask=jnp.arange(cfg.max_classes) < n_cls,
        ta_and_mask=jnp.ones(shape, dtype=bool),
        ta_or_mask=jnp.zeros(shape, dtype=bool),
    )


# ---------------------------------------------------------------------------
# Datapath: literals -> faulted actions -> clauses -> votes (paper Fig. 1)
# ---------------------------------------------------------------------------


def make_literals(x: jax.Array) -> jax.Array:
    """Boolean features -> literal vector [x, ~x] (length 2f)."""
    x = x.astype(bool)
    return jnp.concatenate([x, ~x], axis=-1)


def ta_actions(cfg: TMConfig, state: TMState, rt: TMRuntime) -> jax.Array:
    """Include bits from TA states, with the fault controller applied.

    action = state > N;  action' = (action & and_mask) | or_mask  (§3.1.2).
    """
    include = state.ta_state > cfg.n_states
    return (include & rt.ta_and_mask) | rt.ta_or_mask


def make_literals_packed(xs_packed: jax.Array, n_features: int) -> jax.Array:
    """Packed features [..., ceil(f/32)] u32 -> packed literals (§13 layout).

    The packed twin of :func:`make_literals`: the complement half is a word
    operation, so buffered packed rows become literal words without unpacking.
    """
    return packing.literals_from_packed(xs_packed, n_features)


def ta_actions_packed(cfg: TMConfig, state: TMState, rt: TMRuntime) -> jax.Array:
    """Post-fault include masks, packed to uint32 words (§13 layout).

    This is the include-mask derivation boundary of the packed datapath: the
    int8 TA bank stays unpacked (feedback needs per-literal state), and the
    include plane packs ONCE per batched clause-eval call — O(C·J·L) pack
    work amortized over O(B·C·J·W) evaluation work.
    """
    return packing.pack_include(ta_actions(cfg, state, rt), cfg.n_features)


def clause_polarity(cfg: TMConfig) -> jax.Array:
    """+1 for even-indexed clauses, -1 for odd (half vote for, half against)."""
    return jnp.where(jnp.arange(cfg.max_clauses) % 2 == 0, 1, -1).astype(jnp.int32)


def eval_clauses(
    cfg: TMConfig,
    include: jax.Array,   # [C, J, 2f] bool  (post-fault actions)
    literals: jax.Array,  # [2f] bool
    rt: TMRuntime,
    *,
    training: bool,
) -> jax.Array:
    """Clause outputs [C, J] bool.

    A clause fires iff every included literal is 1. Empty clauses output 1
    during training (so Type I feedback can grow them) and 0 during inference
    (standard TM convention; the paper inherits it from [5]).
    """
    out = dispatch.resolve(cfg.backend).clause_eval(
        include, literals, training=training
    )
    return out & rt.clause_mask[None, :]


def eval_clauses_batch(
    cfg: TMConfig,
    include: jax.Array,   # [C, J, 2f] bool  (post-fault actions)
    literals: jax.Array,  # [B, 2f] bool
    rt: TMRuntime,
    *,
    training: bool,
) -> jax.Array:
    """Batch-first clause outputs [B, C, J] bool.

    The include bank is streamed once per batch (not once per datapoint);
    semantics are row-wise identical to :func:`eval_clauses`.
    """
    out = dispatch.resolve(cfg.backend).clause_eval_batch(
        include, literals, training=training
    )
    return out & rt.clause_mask[None, None, :]


def eval_clauses_batch_packed(
    cfg: TMConfig,
    include_packed: jax.Array,   # [C, J, W] uint32 (packed post-fault actions)
    literals_packed: jax.Array,  # [B, W] uint32
    rt: TMRuntime,
    *,
    training: bool,
) -> jax.Array:
    """Batch-first clause outputs [B, C, J] bool from the packed datapath.

    Bit-identical to :func:`eval_clauses_batch` on the corresponding
    unpacked operands (the kernel contract's packed parity guarantee).
    """
    out = dispatch.resolve(cfg.backend).clause_eval_batch_packed(
        include_packed, literals_packed, training=training
    )
    return out & rt.clause_mask[None, None, :]


def class_sums(cfg: TMConfig, clause_out: jax.Array) -> jax.Array:
    """Per-class vote: sum of +/- polarity clause outputs over the last axis.

    clause_out [..., C, J] -> votes [..., C] int32 (works for single
    datapoints and batch-first [B, C, J] planes alike).
    """
    pol = clause_polarity(cfg)
    return jnp.sum(clause_out.astype(jnp.int32) * pol, axis=-1)


def forward(
    cfg: TMConfig,
    state: TMState,
    rt: TMRuntime,
    x: jax.Array,
    *,
    training: bool = False,
):
    """One datapoint through the datapath. Returns (clause_out [C,J], votes [C])."""
    lits = make_literals(x)
    include = ta_actions(cfg, state, rt)
    clauses = eval_clauses(cfg, include, lits, rt, training=training)
    return clauses, class_sums(cfg, clauses)


def forward_batch(
    cfg: TMConfig,
    state: TMState,
    rt: TMRuntime,
    xs: jax.Array,  # [B, f] bool
    *,
    training: bool = False,
):
    """A batch through the datapath. Returns (clause_out [B,C,J], votes [B,C]).

    ``xs`` is either bool features [B, f] or PACKED features
    [B, ceil(f/32)] uint32 (§13) — the dtype is static under tracing, so
    the branch specializes per representation and packed callers
    (buffer-fed monitoring, packed serving/analysis) route to the
    AND+popcount kernels with no call-site changes. Outputs are
    bit-identical across the two routes.
    """
    if xs.dtype == jnp.uint32:
        lits = make_literals_packed(xs, cfg.n_features)
        include = ta_actions_packed(cfg, state, rt)
        clauses = eval_clauses_batch_packed(
            cfg, include, lits, rt, training=training
        )
    else:
        lits = make_literals(xs)
        include = ta_actions(cfg, state, rt)
        clauses = eval_clauses_batch(cfg, include, lits, rt, training=training)
    return clauses, class_sums(cfg, clauses)


def predict(cfg: TMConfig, state: TMState, rt: TMRuntime, x: jax.Array) -> jax.Array:
    """argmax class over active classes (inactive classes vote -inf)."""
    _, votes = forward(cfg, state, rt, x, training=False)
    votes = jnp.where(rt.class_mask, votes, jnp.iinfo(jnp.int32).min)
    return jnp.argmax(votes)


def predict_batch_(
    cfg: TMConfig, state: TMState, rt: TMRuntime, xs: jax.Array
) -> jax.Array:
    """Unjitted batch-first prediction [B] (composable inside other jits)."""
    _, votes = forward_batch(cfg, state, rt, xs, training=False)
    votes = jnp.where(rt.class_mask[None, :], votes, jnp.iinfo(jnp.int32).min)
    return jnp.argmax(votes, axis=-1)


@partial(jax.jit, static_argnums=0)
def predict_batch(
    cfg: TMConfig, state: TMState, rt: TMRuntime, xs: jax.Array
) -> jax.Array:
    """Batch-first inference over a batch of datapoints (the serving path).

    The clause plane for all B datapoints is one dispatched
    ``clause_eval_batch`` call — the include bank is read once per batch —
    rather than a vmap of per-sample :func:`predict` planes.
    """
    return predict_batch_(cfg, state, rt, xs)


def forward_batch_replicated(
    cfg: TMConfig,
    state: TMState,     # leaves [R, ...]
    rt: TMRuntime,      # masks shared; s/T scalar or [R]
    xs: jax.Array,      # [D, B, f] bool — replica r reads batch r % D
    *,
    training: bool = False,
):
    """Replica-first batch datapath: (clause_out [R,B,C,J], votes [R,B,C]).

    R independent machines evaluate their batch in ONE dispatched
    ``clause_eval_batch_replicated`` contraction; replica ``r`` reproduces
    :func:`forward_batch` on batch ``r % D`` bit-for-bit (the kernel
    contract's stacking guarantee). ``xs`` may be PACKED features
    [D, B, ceil(f/32)] uint32 (§13) — dtype routing, bit-identical.
    """
    if xs.dtype == jnp.uint32:
        lits = make_literals_packed(xs, cfg.n_features)  # [D, B, W]
        include = ta_actions_packed(cfg, state, rt)      # [R, C, J, W]
        clauses = dispatch.resolve(
            cfg.backend
        ).clause_eval_batch_replicated_packed(include, lits, training=training)
    else:
        lits = make_literals(xs)                        # [D, B, 2f]
        include = ta_actions(cfg, state, rt)            # [R, C, J, L]
        clauses = dispatch.resolve(cfg.backend).clause_eval_batch_replicated(
            include, lits, training=training
        )                                               # [R, B, C, J]
    clauses = clauses & rt.clause_mask
    return clauses, class_sums(cfg, clauses)            # [R, B, C]


def predict_batch_replicated_(
    cfg: TMConfig,
    state: TMState,     # leaves [R, ...]
    rt: TMRuntime,      # masks shared; s/T scalar or [R]
    xs: jax.Array,      # [D, B, f] bool — replica r predicts batch r % D
) -> jax.Array:
    """Unjitted replica-first prediction [R, B] (composable inside jits).

    The fleet serving path: :func:`forward_batch_replicated` + the active-
    class argmax (inactive classes vote -inf), per replica.
    """
    _, votes = forward_batch_replicated(cfg, state, rt, xs, training=False)
    votes = jnp.where(rt.class_mask, votes, jnp.iinfo(jnp.int32).min)
    return jnp.argmax(votes, axis=-1)                   # [R, B]


@partial(jax.jit, static_argnums=0)
def predict_batch_replicated(
    cfg: TMConfig, state: TMState, rt: TMRuntime, xs: jax.Array
) -> jax.Array:
    """Jitted :func:`predict_batch_replicated_` — the fleet ``infer`` entry."""
    return predict_batch_replicated_(cfg, state, rt, xs)


# ---------------------------------------------------------------------------
# Budgeted (pruned / weighted) inference — DESIGN.md §16.
# ---------------------------------------------------------------------------


def vote_weights(
    cfg: TMConfig, rt: TMRuntime, weights: Optional[jax.Array] = None
) -> jax.Array:
    """Signed per-clause vote weights: polarity x clause_mask x |weight|.

    ``weights`` is an optional [.., C, J] int plane of positive magnitudes
    (None = unit weights). Returns [C, J] (or [.., C, J]) int32 such that
    ``votes = sum_j clause_out[.., c, j] * vote_weights(...)[.., c, j]``
    reproduces :func:`class_sums` on mask-gated outputs exactly when
    weights are unit — the bitwise bridge between the budgeted vote and
    the plain serving path.
    """
    base = clause_polarity(cfg) * rt.clause_mask.astype(jnp.int32)   # [J]
    if weights is None:
        return jnp.broadcast_to(base, (cfg.max_classes, cfg.max_clauses))
    return weights.astype(jnp.int32) * base


def forward_batch_pruned(
    cfg: TMConfig,
    state: TMState,
    rt: TMRuntime,
    xs: jax.Array,       # [B, f] bool | [B, ceil(f/32)] uint32
    sel: jax.Array,      # [C, M] int32 — clause ids to evaluate, per class
    weights: Optional[jax.Array] = None,  # [C, J] int magnitudes (None = unit)
):
    """Budgeted batch datapath: (clause_out [B,C,M], votes [B,C] i32).

    Only the ``sel``-elected clauses are contracted (compacted include
    banks — the kernel contract's pruned entries), and the class vote
    folds the signed :func:`vote_weights` of the elected clauses. With
    ``sel`` a full permutation and unit weights the int32 vote sums are
    term-for-term a reordering of :func:`forward_batch`'s — bitwise
    identical votes, hence bitwise identical predictions.
    """
    kb = dispatch.resolve(cfg.backend)
    if xs.dtype == jnp.uint32:
        lits = make_literals_packed(xs, cfg.n_features)
        include = ta_actions_packed(cfg, state, rt)
        clauses = kb.clause_eval_batch_pruned_packed(
            include, sel, lits, training=False
        )
    else:
        lits = make_literals(xs)
        include = ta_actions(cfg, state, rt)
        clauses = kb.clause_eval_batch_pruned(
            include, sel, lits, training=False
        )                                                  # [B, C, M]
    swt = vote_weights(cfg, rt, weights)                   # [C, J]
    wsel = jnp.take_along_axis(swt, sel, axis=-1)          # [C, M]
    votes = jnp.sum(clauses.astype(jnp.int32) * wsel[None], axis=-1)
    return clauses, votes


def predict_batch_pruned_(
    cfg: TMConfig, state: TMState, rt: TMRuntime, xs: jax.Array,
    sel: jax.Array, weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Unjitted budgeted prediction [B] (composable inside other jits)."""
    _, votes = forward_batch_pruned(cfg, state, rt, xs, sel, weights)
    votes = jnp.where(rt.class_mask[None, :], votes, jnp.iinfo(jnp.int32).min)
    return jnp.argmax(votes, axis=-1)


@partial(jax.jit, static_argnums=0)
def predict_batch_pruned(
    cfg: TMConfig, state: TMState, rt: TMRuntime, xs: jax.Array,
    sel: jax.Array, weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Jitted :func:`predict_batch_pruned_` — the budgeted serving entry."""
    return predict_batch_pruned_(cfg, state, rt, xs, sel, weights)


def forward_batch_pruned_replicated(
    cfg: TMConfig,
    state: TMState,      # leaves [R, ...]
    rt: TMRuntime,
    xs: jax.Array,       # [D, B, ...] — replica r reads batch r % D
    sel: jax.Array,      # [R, C, M] int32 — per-replica clause rankings
    weights: Optional[jax.Array] = None,  # [R, C, J] int magnitudes
):
    """Replica-first budgeted datapath: (clauses [R,B,C,M], votes [R,B,C]).

    Every replica serves from its OWN ranked clause subset (and weight
    plane) in one contraction over the compacted banks.
    """
    kb = dispatch.resolve(cfg.backend)
    if xs.dtype == jnp.uint32:
        lits = make_literals_packed(xs, cfg.n_features)
        include = ta_actions_packed(cfg, state, rt)
        clauses = kb.clause_eval_batch_pruned_replicated_packed(
            include, sel, lits, training=False
        )
    else:
        lits = make_literals(xs)
        include = ta_actions(cfg, state, rt)
        clauses = kb.clause_eval_batch_pruned_replicated(
            include, sel, lits, training=False
        )                                                  # [R, B, C, M]
    swt = vote_weights(cfg, rt, weights)
    if swt.ndim == 2:
        swt = jnp.broadcast_to(swt, sel.shape[:1] + swt.shape)
    wsel = jnp.take_along_axis(swt, sel, axis=-1)          # [R, C, M]
    votes = jnp.sum(clauses.astype(jnp.int32) * wsel[:, None], axis=-1)
    return clauses, votes                                  # votes [R, B, C]


def predict_batch_pruned_replicated_(
    cfg: TMConfig, state: TMState, rt: TMRuntime, xs: jax.Array,
    sel: jax.Array, weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Unjitted replica-first budgeted prediction [R, B]."""
    _, votes = forward_batch_pruned_replicated(
        cfg, state, rt, xs, sel, weights
    )
    votes = jnp.where(rt.class_mask, votes, jnp.iinfo(jnp.int32).min)
    return jnp.argmax(votes, axis=-1)


@partial(jax.jit, static_argnums=0)
def predict_batch_pruned_replicated(
    cfg: TMConfig, state: TMState, rt: TMRuntime, xs: jax.Array,
    sel: jax.Array, weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Jitted :func:`predict_batch_pruned_replicated_` — the fleet's
    budgeted serve entry (TMService.serve with a compute budget)."""
    return predict_batch_pruned_replicated_(cfg, state, rt, xs, sel, weights)
