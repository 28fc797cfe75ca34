"""Tiny cells for the CPU rehearsal: the real configuration and traffic
files with the scale cut to what a test run holds (K=8 tenants), and a
digits machine built from the iris file in code, so that the data kind and
the reference a configuration names are exercised end to end."""
from __future__ import annotations

import copy

import pytest

from bench import peaks, setup


def cell(config: str, traffic: str, K: int = 8, conf_over=None):
    """``conf_over`` maps a configuration key to a value, or a group's key
    to the entries of that group it replaces."""
    conf = copy.deepcopy(setup.load_config(config))
    tr = copy.deepcopy(setup.load_traffic(traffic))
    conf["tenants"] = K
    for k, v in (conf_over or {}).items():
        if isinstance(v, dict):
            conf[k].update(v)
        else:
            conf[k] = v
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


# 7x7 digits (f=49, 10 classes); 150 rows cover the iris file's split
DIGITS = {"data": {"kind": "digits", "side": 7, "n_points": 150},
          "machine": {"n_features": 49, "max_classes": 10}}

CELLS = {
    "iris-catchup": ("tm-iris-paper-k4096", "catchup", {}),
    "digits-catchup": ("tm-iris-paper-k4096", "catchup", DIGITS),
}


def named(name: str, **conf_over):
    config, traffic, over = CELLS[name]
    return cell(config, traffic, conf_over={**over, **conf_over})
