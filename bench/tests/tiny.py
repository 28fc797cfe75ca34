"""Tiny cells for the CPU rehearsal: the real configuration and traffic
files with the scale cut to what a test run holds (K=8 tenants)."""
from __future__ import annotations

import copy

import pytest

from bench import peaks, setup


def cell(config: str, traffic: str, K: int = 8, **traffic_over):
    conf = copy.deepcopy(setup.load_config(config))
    tr = copy.deepcopy(setup.load_traffic(traffic))
    conf["tenants"] = K
    tr.update(traffic_over)
    w = {"name": f"tiny-{traffic}", "config": config, "traffic": traffic,
         "chips": 1}
    return w, conf, tr


@pytest.fixture
def cpu_run(monkeypatch):
    """run_cell on the CPU: the look for a chip is skipped, the CPU borrows
    the v5e peaks, and no persistent compile cache is written."""
    import jax

    from bench import run

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    jax.config.update("jax_enable_compilation_cache", False)

    def go(c, seconds=2.0, trace=False, control=False, seed=2**31 + 7):
        return run.run_cell(c[0]["name"], seed, seconds, trace, cell=c,
                            platform="cpu", control=control)
    return go


CELLS = {
    "iris-catchup": ("tm-iris-paper-k4096", "catchup", {}),
}


def named(name: str):
    config, traffic, over = CELLS[name]
    return cell(config, traffic, **over)
