"""The least work the Tsetlin-machine algorithm needs, whatever runs it.

Counted from the algorithm, not from what today's kernels move: each
tenant trained in a tick has its TA bank read once and written once,
``2 * C * J * 2f`` bytes at int8, plus its packed rows (``4 * ceil(f / 32)``
bytes each).

v5e publishes no vector-unit rate, so the bound is bytes over peak HBM
bandwidth alone.
"""
from __future__ import annotations


def _machine(conf: dict) -> tuple[int, int, int, int]:
    m = conf["machine"]
    state_bytes = 1 if 2 * m["n_states"] <= 127 else 2
    return m["max_classes"], m["max_clauses"], m["n_features"], state_bytes


def row_bytes(conf: dict) -> int:
    f = conf["machine"]["n_features"]
    return 4 * (-(-f // 32))


def bank_bytes_per_trained_tenant(conf: dict) -> int:
    """TA bank read + write for one tenant trained in one tick."""
    C, J, f, b = _machine(conf)
    return 2 * C * J * 2 * f * b


def train_bytes(conf: dict, tenant_ticks: int, rows: int) -> int:
    return (tenant_ticks * bank_bytes_per_trained_tenant(conf)
            + rows * row_bytes(conf))


def least_seconds(n_bytes: float, peak: dict) -> float:
    return n_bytes / peak["hbm_bytes_per_s"]
