"""The least work of a Convolutional Tsetlin machine, whatever runs it.

Counted from the algorithm (arXiv:1905.09688), not from what today's kernels
move:

* patch evaluation: every clause's conjunction on every patch of a row is
  ``2 * P * C * J * 2f'`` int8 operations as a 0/1 contraction (P patches,
  2f' literals a patch), per row evaluated, at v5e's int8 peak;
* feedback: each tenant trained in a tick has its TA bank read once and
  written once, ``2 * C * J * 2f' * b`` bytes (b bytes a state), at peak HBM
  bandwidth.

The machine's numbers come from the configuration's ``machine`` group
(``image``, ``window``, ``max_classes``, ``max_clauses``, ``n_states``).
"""
from __future__ import annotations


def _grid(conf: dict) -> tuple[int, int]:
    m = conf["machine"]
    (h, w), (wh, ww) = m["image"], m["window"]
    return h - wh + 1, w - ww + 1


def n_patches(conf: dict) -> int:
    py, px = _grid(conf)
    return py * px


def n_literals(conf: dict) -> int:
    """2f': a patch's window pixels and its x and y thermometer bits,
    and their negations."""
    m = conf["machine"]
    py, px = _grid(conf)
    return 2 * (m["window"][0] * m["window"][1] + (px - 1) + (py - 1))


def _clauses(conf: dict) -> int:
    m = conf["machine"]
    return m["max_classes"] * m["max_clauses"]


def patch_eval_ops_per_row(conf: dict) -> int:
    return 2 * n_patches(conf) * _clauses(conf) * n_literals(conf)


def feedback_bytes_per_tenant_tick(conf: dict) -> int:
    state_bytes = 1 if 2 * conf["machine"]["n_states"] <= 127 else 2
    return 2 * _clauses(conf) * n_literals(conf) * state_bytes


def patch_eval_seconds(conf: dict, rows: float, peak: dict) -> float:
    """Least time of the patch evaluation of ``rows`` rows."""
    return rows * patch_eval_ops_per_row(conf) / peak["int8_ops"]


def feedback_seconds(conf: dict, tenant_ticks: float, peak: dict) -> float:
    """Least time of the feedback of ``tenant_ticks`` trained tenant-ticks."""
    return (tenant_ticks * feedback_bytes_per_tenant_tick(conf)
            / peak["hbm_bytes_per_s"])
