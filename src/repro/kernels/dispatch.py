"""Backend dispatch: ONE seam between the TM core and its kernel backends.

The paper's FPGA fixes its datapath at synthesis; here the datapath
implementation is chosen at trace time through a registry keyed by
``TMConfig.backend``:

* ``"ref"``    — pure-jnp oracles (:mod:`repro.kernels.ref`). CPU default and
  the semantic ground truth every other backend is asserted bit-exact against.
* ``"pallas"`` — TPU Pallas kernels (:mod:`repro.kernels.ops`): MXU clause
  matmul + fused VPU feedback plane. :func:`resolve` decides interpret mode
  here, once, from the platform: compiled Mosaic kernels on a TPU, the
  Pallas interpreter everywhere else.
* ``"auto"``   — resolves to ``pallas`` when JAX is running on a TPU,
  ``ref`` otherwise (off the chip, ``TM_BACKEND`` may name another backend).

Every backend implements the same typed contract, :class:`KernelBackend`,
and the contract is **batch-first**: ``clause_eval_batch`` takes ``[B, L]``
literals and returns ``[B, C, J]`` clause outputs with the include bank
streamed once per *batch* (not once per datapoint — see DESIGN.md §8).
Future backends (sharded, multi-device, GPU) plug in via :func:`register`
without touching the core.

This module is the ONLY place allowed to know which concrete kernel module
backs which name; ``if cfg.backend == ...`` branches anywhere else are a bug.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Callable, NamedTuple

import jax


class KernelBackend(NamedTuple):
    """The typed kernel contract every backend must implement.

    All entries are pure, trace-compatible functions. Two orthogonal axes
    run through the contract: *batch-first* entries stream many datapoints
    through ONE machine, *replica-first* entries stream one datapoint each
    through MANY independent machines — the cross-validation /
    hyperparameter sweep axis (paper §3.6.1/§5, DESIGN.md §9) and, since
    the serving layer became the contract's third consumer, the online
    fleet axis (K concurrent Fig-3 sessions, DESIGN.md §10). Replica-first operands
    follow one layout rule: per-replica state/control carries a leading
    ``R``; per-data-stream operands (literals, uniforms) carry a leading
    ``D`` with ``D | R``, and replica ``r`` reads data row ``r % D`` — so a
    hyperparameter grid over shared data stores each draw once.

    * ``clause_eval(include [C,J,L] bool, literals [L] bool, *, training)
      -> [C,J] bool`` — one datapoint's clause plane.
    * ``clause_eval_batch(include [C,J,L] bool, literals [B,L] bool, *,
      training) -> [B,C,J] bool`` — the batch-first entry point; MUST equal
      stacking ``clause_eval`` over rows bit-for-bit.
    * ``clause_eval_replicated(include [R,C,J,L], literals [D,L], *,
      training) -> [R,C,J]`` — replica-first clause plane; MUST equal
      stacking ``clause_eval(include[r], literals[r % D])`` bit-for-bit.
    * ``clause_eval_batch_replicated(include [R,C,J,L], literals [D,B,L], *,
      training) -> [R,B,C,J]`` — replica-first analysis/serving pass (the
      sweep's fused multi-set analysis AND the fleet ``infer`` path run on
      this entry; pallas: one 3-D (replica, clause-block, column-block)
      grid with ``r % D`` rhs index maps); MUST equal stacking
      ``clause_eval_batch`` per replica bit-for-bit.
    * ``clause_eval_batch_packed(include_packed [C,J,W] uint32,
      literals_packed [B,W] uint32, *, training) -> [B,C,J]`` — the
      bit-packed datapath (DESIGN.md §13): W = 2*ceil(f/32) words per the
      two-half layout in :mod:`repro.kernels.packing`, clause eval as
      AND + popcount (``fires <=> sum_w popcount(inc & ~lit) == 0``). MUST
      equal ``clause_eval_batch`` on the corresponding unpacked operands
      bit-for-bit — the unpacked entry is the packed path's parity oracle.
    * ``clause_eval_batch_replicated_packed(include_packed [R,C,J,W],
      literals_packed [D,B,W], *, training) -> [R,B,C,J]`` — replica-first
      packed analysis/serving pass, same ``r % D`` data-stream rule; MUST
      equal ``clause_eval_batch_replicated`` on unpacked operands.
    * ``clause_eval_batch_pruned(include [C,J,L], sel [C,M] i32,
      literals [B,L], *, training) -> [B,C,M]`` — budgeted serve
      (DESIGN.md §16): the include bank compacts to the selected clauses
      (a gather along J) BEFORE the contraction, so compute shrinks with
      the budget M rather than masking. Column m MUST equal
      ``clause_eval_batch(...)[:, c, sel[c, m]]`` bit-for-bit.
    * ``clause_eval_batch_pruned_replicated(include [R,C,J,L],
      sel [R,C,M], literals [D,B,L], *, training) -> [R,B,C,M]`` —
      replica-first budgeted serve; replica ``r`` reads batch ``r % D``
      and its OWN per-replica ranking ``sel[r]``.
    * ``clause_eval_batch_pruned_packed(include_packed [C,J,W] u32,
      sel [C,M], literals_packed [B,W] u32, *, training) -> [B,C,M]`` and
      ``clause_eval_batch_pruned_replicated_packed([R,C,J,W], [R,C,M],
      [D,B,W], *, training) -> [R,B,C,M]`` — the packed twins (the gather
      never touches the word axis, so the §13 tail-bits-zero contract is
      preserved and packed pruned MUST equal unpacked pruned bit-for-bit).
    * ``feedback_step(ta_state [C,J,L], literals [L], clause_out [C,J],
      type1_sel [C,J], type2_sel [C,J], u [C,J,L], *, s, n_states, s_policy,
      boost_true_positive) -> new ta_state`` — one datapoint's TA update.
    * ``feedback_step_replicated(ta_state [R,C,J,L], literals [D,L],
      clause_out [R,C,J], type1_sel [R,C,J], type2_sel [R,C,J], u [D,C,J,L],
      *, s [R], n_states, s_policy, boost_true_positive) -> [R,C,J,L]`` —
      R independent TA-bank updates in one fused plane (ref: one [R, C·J, L]
      elementwise pass; pallas: a 2-D (replica, clause-block) grid); MUST
      equal stacking ``feedback_step`` per replica bit-for-bit.

    Patch machines (the Convolutional TM, DESIGN.md §18) have two entries of
    their own; a plain machine never calls them:

    * ``patch_clause_eval_replicated(include [R,C,J,L] bool,
      literals [D,B,P,L] bool, u [D,B,C,J] f32 | None, *, training)
      -> (clause_out [R,B,C,J] bool, chosen [R,B,C,J] i32 | None)`` — each
      clause's conjunction on every one of a row's P patches, OR-ed over
      the patches (empty clauses as in ``clause_eval``). With ``u`` (one
      uniform per clause per row), ``chosen`` is the
      ``min(floor(u*m), m-1)``-th of the m patches the clause fired on, in
      patch order, and 0 when m = 0. Replica ``r`` reads batch ``r % D``;
      MUST equal stacking the entry per replica and per row bit-for-bit
      (pallas: a (replica, clause-block, row) grid, compute-bound).
    * ``feedback_patch_replicated(ta_state [R,C,J,L], literals [R,C,J,L],
      clause_out [R,C,J], type1_sel, type2_sel, u [D,C,J,L], *, s [R],
      n_states, s_policy, boost_true_positive) -> [R,C,J,L]`` —
      ``feedback_step_replicated`` with one literal row per clause (the
      chosen patch's); MUST equal that entry wherever every clause's row
      equals the replica's one row.
    """

    name: str
    interpret: bool  # pallas: kernels run in the Pallas interpreter
    clause_eval: Callable[..., jax.Array]
    clause_eval_batch: Callable[..., jax.Array]
    clause_eval_replicated: Callable[..., jax.Array]
    clause_eval_batch_replicated: Callable[..., jax.Array]
    clause_eval_batch_packed: Callable[..., jax.Array]
    clause_eval_batch_replicated_packed: Callable[..., jax.Array]
    clause_eval_batch_pruned: Callable[..., jax.Array]
    clause_eval_batch_pruned_replicated: Callable[..., jax.Array]
    clause_eval_batch_pruned_packed: Callable[..., jax.Array]
    clause_eval_batch_pruned_replicated_packed: Callable[..., jax.Array]
    feedback_step: Callable[..., jax.Array]
    feedback_step_replicated: Callable[..., jax.Array]
    patch_clause_eval_replicated: Callable[..., tuple]
    feedback_patch_replicated: Callable[..., jax.Array]


# The 14 kernel entries of the contract (every field after name/interpret).
ENTRIES: tuple[str, ...] = KernelBackend._fields[2:]

# Factories, not instances: "pallas" must not import Pallas machinery unless
# it is actually selected (keeps ref-only environments import-light).
_FACTORIES: dict[str, Callable[[], KernelBackend]] = {}
_CACHE: dict[str, KernelBackend] = {}


def register(name: str, factory: Callable[[], KernelBackend]) -> None:
    """Register (or replace) a backend under ``name``."""
    _FACTORIES[name] = factory
    _CACHE.pop(name, None)


def available() -> tuple[str, ...]:
    """Registered backend names (plus the ``auto`` alias)."""
    return tuple(sorted(_FACTORIES)) + ("auto",)


def _auto_name() -> str:
    if jax.default_backend() == "tpu":
        return "pallas"
    # Off the chip TM_BACKEND may re-route auto (CI runs the kernel/parity
    # suites a second time with TM_BACKEND=pallas, interpreted).
    return os.environ.get("TM_BACKEND") or "ref"


def resolve(name: str) -> KernelBackend:
    """Backend name (or ``"auto"``) -> the :class:`KernelBackend` instance."""
    if name == "auto":
        name = _auto_name()
    if name not in _FACTORIES:
        raise ValueError(
            f"unknown kernel backend {name!r}; available: {available()}"
        )
    if name not in _CACHE:
        _CACHE[name] = _FACTORIES[name]()
    return _CACHE[name]


def _make_ref() -> KernelBackend:
    from repro.kernels import ref

    return KernelBackend(
        name="ref", interpret=False,
        **{e: getattr(ref, e) for e in ENTRIES},
    )


def _make_pallas() -> KernelBackend:
    from repro.kernels import ops

    # Compiled Mosaic kernels on the chip; off it the interpreter is the
    # only way the kernels run.
    interpret = jax.default_backend() != "tpu"
    return KernelBackend(
        name="pallas", interpret=interpret,
        **{e: partial(getattr(ops, e), interpret=interpret) for e in ENTRIES},
    )


register("ref", _make_ref)
register("pallas", _make_pallas)
