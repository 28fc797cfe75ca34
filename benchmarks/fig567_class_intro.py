"""Paper Figures 5-7: unseen class introduction at runtime.

Fig 5 (baseline): class 0 filtered from all sets for the whole run, online
learning enabled — accuracy improves on the 2-class problem.
Fig 6 (baseline): class 0 introduced after 5 online cycles, online learning
DISABLED — accuracy drops and stays down.
Fig 7: introduction after 5 cycles WITH online learning — accuracy dips then
recovers.

Offline set uses its full 30 rows here (the paper: filtering one of three
classes leaves ~20 of 30 — its §5.1 budget — while val/online drop to ~40).
"""
from __future__ import annotations


from benchmarks import common
from repro.core import manager as mgr


def run(n_orderings: int = 24, introduce_at: int = 5, seed: int = 0,
        dataset: str = "iris", side: int | None = None):
    s_onl = common.system_params(dataset, side).s_online
    kw = dict(n_orderings=n_orderings, offline_limit=None, seed=seed,
              dataset=dataset, side=side)
    out = {}
    out["fig5_filtered_online"] = common.run_schedule(
        mgr.make_schedule(online_s=s_onl, filtered_class=0), **kw
    )
    out["fig6_intro_no_online"] = common.run_schedule(
        mgr.make_schedule(online_s=s_onl, filtered_class=0,
                          introduce_at_cycle=introduce_at,
                          online_enabled=False),
        **kw,
    )
    out["fig7_intro_online"] = common.run_schedule(
        mgr.make_schedule(online_s=s_onl, filtered_class=0,
                          introduce_at_cycle=introduce_at),
        **kw,
    )
    return out, introduce_at


def main(n_orderings: int = 24):
    out, intro = run(n_orderings)
    walls = 0.0
    for name, (curve, _act, wall, _O) in out.items():
        print(common.curve_csv(name, curve))
        walls += wall

    c7 = out["fig7_intro_online"][0]
    c6 = out["fig6_intro_no_online"][0]
    # dip at first analysis after introduction; recovery by the end
    dip7 = c7[intro + 1, 1] - c7[intro, 1]
    rec7 = c7[-1, 1] - c7[intro + 1, 1]
    final_gap = c7[-1, 1] - c6[-1, 1]
    us = walls * 1e6 / (3 * len(c7))
    print(f"fig567_class_intro,{us:.0f},"
          f"dip_val={dip7:+.3f};recovery_val={rec7:+.3f};"
          f"online_vs_frozen_final={final_gap:+.3f}")
    return {"dip": dip7, "recovery": rec7, "final_gap": final_gap}


if __name__ == "__main__":
    common.enable_compile_cache()
    main()
