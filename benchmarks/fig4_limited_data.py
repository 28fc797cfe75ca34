"""Paper Figure 4: online learning with labelled data, limited initial set.

Offline: 10 epochs on 20 datapoints (s=1.375). Online: 16 single-pass cycles
over the 60-point online set at s=1.0. Accuracy re-analyzed per cycle on all
three sets, averaged over cross-validation orderings.

Paper claims (iris): starting accuracies ~83% offline / 79.5% validation /
79.5% online; after 16 cycles validation+online rise ~+12%, offline ~+5%.
"""
from __future__ import annotations


from benchmarks import common
from repro.core import manager as mgr


def run(n_orderings: int = 24, seed: int = 0,
        dataset: str = "iris", side: int | None = None):
    params = common.system_params(dataset, side)
    schedule = mgr.make_schedule(online_s=params.s_online)
    curve, activity, wall, O = common.run_schedule(
        schedule, n_orderings=n_orderings, seed=seed,
        dataset=dataset, side=side,
    )
    gains = curve[-1] - curve[0]
    derived = {
        "start_offline": curve[0, 0], "start_val": curve[0, 1],
        "start_online": curve[0, 2],
        "gain_offline": gains[0], "gain_val": gains[1],
        "gain_online": gains[2],
        "mean_activity": float(activity.mean()),
        "orderings": O,
    }
    return curve, derived, wall


def main(n_orderings: int = 24):
    curve, derived, wall = run(n_orderings)
    print(common.curve_csv("fig4", curve))
    us = wall * 1e6 / max(1, len(curve))
    d = (f"start_off={derived['start_offline']:.3f};"
         f"start_val={derived['start_val']:.3f};"
         f"gain_val={derived['gain_val']:+.3f};"
         f"gain_online={derived['gain_online']:+.3f};"
         f"gain_off={derived['gain_offline']:+.3f};"
         f"activity={derived['mean_activity']:.4f}")
    print(f"fig4_limited_data,{us:.0f},{d}")
    return derived


if __name__ == "__main__":
    common.enable_compile_cache()
    main()
