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
and the contract is **replica-first**: every entry takes R machines' banks
at once, and one machine is the R = 1 slice of the same entry (the core's
single-machine functions call it so, and no entry has a single-machine
twin). Future backends (sharded, multi-device, GPU) plug in via
:func:`register` without touching the core.

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

    All entries are pure, trace-compatible functions over MANY independent
    machines: the cross-validation / hyperparameter sweep axis (paper
    §3.6.1/§5, DESIGN.md §9) and the online fleet axis (K concurrent Fig-3
    sessions, DESIGN.md §10). One machine is R = D = 1. Operands follow one
    layout rule: per-replica state/control carries a leading ``R``;
    per-data-stream operands (literals, uniforms) carry a leading ``D``
    with ``D | R``, and replica ``r`` reads data row ``r % D`` — so a
    hyperparameter grid over shared data stores each draw once.

    The stacking guarantees below are stated against the per-machine
    oracles of :mod:`repro.kernels.ref` (``clause_eval``,
    ``clause_eval_loop``, ``feedback_step``), which define a machine.

    * ``clause_eval_replicated(include [R,C,J,L] bool, literals [D,L] bool,
      *, training) -> [R,C,J] bool`` — one datapoint's clause plane per
      replica; MUST equal stacking ``ref.clause_eval(include[r],
      literals[r % D])`` bit-for-bit. A clause fires iff every included
      literal is 1; an empty clause outputs ``training``.
    * ``clause_eval_batch_replicated(include [R,C,J,L], literals [D,B,L], *,
      training) -> [R,B,C,J]`` — the analysis/serving pass (the sweep's
      fused multi-set analysis AND the fleet ``infer`` path run on this
      entry; pallas: one (replica, clause-block, column-block) grid with
      ``r % D`` rhs index maps). The include bank streams once per *batch*
      (DESIGN.md §8); MUST equal stacking ``ref.clause_eval_loop`` per
      replica bit-for-bit.
    * ``clause_eval_batch_replicated_packed(include_packed [R,C,J,W] uint32,
      literals_packed [D,B,W] uint32, *, training) -> [R,B,C,J]`` — the
      bit-packed datapath (DESIGN.md §13): W = 2*ceil(f/32) words per the
      two-half layout in :mod:`repro.kernels.packing`, clause eval as
      AND + popcount (``fires <=> sum_w popcount(inc & ~lit) == 0``). MUST
      equal ``clause_eval_batch_replicated`` on the corresponding unpacked
      operands bit-for-bit — the unpacked entry is its parity oracle.
    * ``clause_eval_batch_pruned_replicated(include [R,C,J,L],
      sel [R,C,M] i32, literals [D,B,L], *, training) -> [R,B,C,M]`` —
      budgeted serve (DESIGN.md §16): the include bank compacts to the
      selected clauses (a gather along J) BEFORE the contraction, so
      compute shrinks with the budget M rather than masking. Replica ``r``
      reads batch ``r % D`` and its OWN ranking ``sel[r]``; column m MUST
      equal ``clause_eval_batch_replicated(...)[r, :, c, sel[r, c, m]]``
      bit-for-bit.
    * ``clause_eval_batch_pruned_replicated_packed([R,C,J,W] u32, [R,C,M],
      [D,B,W] u32, *, training) -> [R,B,C,M]`` — the packed twin (the
      gather never touches the word axis, so the §13 tail-bits-zero
      contract is preserved and packed pruned MUST equal unpacked pruned
      bit-for-bit).
    * ``feedback_step_replicated(ta_state [R,C,J,L], literals [D,L],
      clause_out [R,C,J], type1_sel [R,C,J], type2_sel [R,C,J], u [D,C,J,L],
      *, s [R], n_states, s_policy, boost_true_positive) -> [R,C,J,L]`` —
      R independent TA-bank updates in one fused plane (ref: one [R, C·J, L]
      elementwise pass; pallas: a (replica, clause-block) grid); MUST
      equal stacking ``ref.feedback_step`` per replica bit-for-bit.

    Patch machines (the Convolutional TM, DESIGN.md §18) have two entries of
    their own; a plain machine never calls them:

    * ``patch_clause_eval_replicated(include [R,C,J,L] bool,
      literals [D,B,P,L] bool, u [D,B,C,J] f32 | None, *, training)
      -> (clause_out [R,B,C,J] bool, chosen [R,B,C,J] i32 | None)`` — each
      clause's conjunction on every one of a row's P patches, OR-ed over
      the patches (empty clauses as in ``clause_eval_replicated``). With
      ``u`` (one uniform per clause per row), ``chosen`` is the
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
    clause_eval_replicated: Callable[..., jax.Array]
    clause_eval_batch_replicated: Callable[..., jax.Array]
    clause_eval_batch_replicated_packed: Callable[..., jax.Array]
    clause_eval_batch_pruned_replicated: Callable[..., jax.Array]
    clause_eval_batch_pruned_replicated_packed: Callable[..., jax.Array]
    feedback_step_replicated: Callable[..., jax.Array]
    patch_clause_eval_replicated: Callable[..., tuple]
    feedback_patch_replicated: Callable[..., jax.Array]


# The 8 kernel entries of the contract (every field after name/interpret).
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
