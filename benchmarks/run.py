"""Benchmark orchestrator. One entry per paper table/figure; prints
``name,us_per_call,derived`` CSV rows (plus per-figure accuracy curves).

Env knobs:
  REPRO_BENCH_ORDERINGS   cross-validation orderings (default 24; paper 120)
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> None:
    n_ord = int(os.environ.get("REPRO_BENCH_ORDERINGS", "24"))
    print(f"# benchmarks (orderings={n_ord}); csv: name,us_per_call,derived")
    ok = True

    t0 = time.time()
    from benchmarks import crossval as crossval_bench
    from benchmarks import fig4_limited_data, fig567_class_intro, fig89_faults
    from benchmarks import fleet as fleet_bench
    from benchmarks import throughput

    for name, fn in [
        ("fig4", lambda: fig4_limited_data.main(n_ord)),
        ("fig567", lambda: fig567_class_intro.main(n_ord)),
        ("fig89", lambda: fig89_faults.main(n_ord)),
        ("throughput", throughput.main),
        ("crossval", lambda: crossval_bench.main(n_ord)),
        ("fleet", fleet_bench.main),
    ]:
        try:
            fn()
        except Exception:
            ok = False
            print(f"{name},0,ERROR")
            traceback.print_exc()

    print(f"# total wall: {time.time()-t0:.1f}s")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    from benchmarks import common

    common.enable_compile_cache()
    main()
