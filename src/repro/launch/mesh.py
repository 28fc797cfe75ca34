"""Production mesh construction.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before the first jax init).

Mesh axes:
  pod   — across pods (2 in the multi-pod dry-run); DP/FSDP outer axis
  data  — within-pod data parallel / FSDP axis (16)
  model — TP / EP / SP axis (16; maps to the v5e 2D torus's second dim)
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # The models shard through GSPMD (with_sharding_constraint hints), which
    # needs Auto axes; jax.make_mesh defaults to Explicit ones.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, n_pods: int = 2):
    shape = (n_pods, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return _auto_mesh((n // model, model), ("data", "model"))
