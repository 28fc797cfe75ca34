"""The Convolutional TM's cell at a tiny size on the CPU: the digits at side
12 under a 4x4 window (81 patches) through ``run_cell``, checked exactly
against ``bench/ctm_reference.py``; the bfloat16 control fails; and the
least-work counts of ``bench/work_ctm.py`` against hand figures."""
import pytest

from bench import peaks, setup, work_ctm
from bench.tests.tiny import cell, cpu_run  # noqa: F401

CONFIG = "ctm-digits28-w10"
TINY = {"data": {"side": 12},
        "machine": {"n_features": 144, "image": [12, 12], "window": [4, 4],
                    "max_clauses": 8}}


def test_tiny_ctm_cell_runs_correct_and_control_fails(cpu_run):  # noqa: F811
    res = cpu_run(cell(CONFIG, "catchup", K=8, conf_over=TINY), seconds=2.0,
                  control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert res["control"]["bank_mismatch"] > 0, res["control"]


def test_tiny_ctm_traced_run_reads_the_patch_metrics(cpu_run):  # noqa: F811
    import json

    c = cell(CONFIG, "catchup", K=8, conf_over=TINY)
    c[0]["name"] = "ctm-digits28-w10-catchup"   # the metrics' workload
    res = cpu_run(c, seconds=1.0, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # the CPU's trace has no Pallas kernel events; the counter reads
    assert "patch_evals_per_row" in m, m
    assert m["patch_evals_per_row"]["value"] >= 81
    json.dumps(res)


def test_patch_eval_ops_hand_count():
    conf = setup.load_config(CONFIG)
    # 2 ops x 361 patches x 10 x 2000 clauses x 272 literals
    assert work_ctm.n_patches(conf) == 361
    assert work_ctm.n_literals(conf) == 272
    assert work_ctm.patch_eval_ops_per_row(conf) == 2 * 361 * 20000 * 272
    tiny = {"machine": {**conf["machine"], **TINY["machine"]}}
    # 12x12 under 4x4: 9 x 9 patches of 16 + 8 + 8 features
    assert work_ctm.n_patches(tiny) == 81
    assert work_ctm.patch_eval_ops_per_row(tiny) == 2 * 81 * 10 * 8 * 64


def test_feedback_bytes_hand_count():
    conf = setup.load_config(CONFIG)
    # int16 bank of 10 x 2000 x 272 read and written once per tenant-tick
    assert work_ctm.feedback_bytes_per_tenant_tick(conf) == \
        2 * 10 * 2000 * 272 * 2
    p = peaks.peaks("TPU v5 lite")
    assert work_ctm.patch_eval_seconds(conf, 10, p) == pytest.approx(
        10 * 2 * 361 * 20000 * 272 / 393e12)
    assert work_ctm.feedback_seconds(conf, 3, p) == pytest.approx(
        3 * 21_760_000 / 819e9)
