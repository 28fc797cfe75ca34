"""Paper Figures 8-9: fault mitigation via online learning.

20% of TAs forced stuck-at-0 (evenly spread, §5.3.1) after 5 online cycles.
Fig 8: online learning disabled — accuracy falls and stays down.
Fig 9: online learning enabled — accuracy dips then recovers toward the
fault-free trajectory (paper: final gains on par with Figure 4).
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmarks import common
from repro.core import faults as faults_mod
from repro.core import manager as mgr


def run(n_orderings: int = 24, inject_at: int = 5, fraction: float = 0.2,
        seed: int = 0, dataset: str = "iris", side: int | None = None):
    params = common.system_params(dataset, side)
    and_m, or_m = faults_mod.even_spread_stuck_at(params.tm, fraction, 0)
    masks = (jnp.asarray(and_m), jnp.asarray(or_m))
    kw = dict(n_orderings=n_orderings, seed=seed, dataset=dataset, side=side)
    out = {}
    out["fig8_faults_no_online"] = common.run_schedule(
        mgr.make_schedule(online_s=params.s_online, fault_masks=masks,
                          inject_at_cycle=inject_at, online_enabled=False),
        **kw,
    )
    out["fig9_faults_online"] = common.run_schedule(
        mgr.make_schedule(online_s=params.s_online, fault_masks=masks,
                          inject_at_cycle=inject_at),
        **kw,
    )
    return out, inject_at


def main(n_orderings: int = 24):
    out, inject = run(n_orderings)
    walls = 0.0
    for name, (curve, _act, wall, _O) in out.items():
        print(common.curve_csv(name, curve))
        walls += wall

    c8 = out["fig8_faults_no_online"][0]
    c9 = out["fig9_faults_online"][0]
    drop8 = c8[inject + 1, 1] - c8[inject, 1]
    dip9 = c9[inject + 1, 1] - c9[inject, 1]
    rec9 = c9[-1, 1] - c9[inject + 1, 1]
    final_gap = c9[-1, 1] - c8[-1, 1]
    us = walls * 1e6 / (2 * len(c9))
    print(f"fig89_faults,{us:.0f},"
          f"frozen_drop={drop8:+.3f};online_dip={dip9:+.3f};"
          f"online_recovery={rec9:+.3f};online_vs_frozen={final_gap:+.3f}")
    return {"drop8": drop8, "dip9": dip9, "rec9": rec9,
            "final_gap": final_gap}


if __name__ == "__main__":
    common.enable_compile_cache()
    main()
