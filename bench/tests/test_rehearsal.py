"""Each traffic mix end to end on the CPU at a tiny size: the generator,
the consumer and client loops, the check against the reference, the last
line's keys, and the control, which must fail the check."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench.tests.tiny import CELLS, cpu_run, named  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_mix_runs_correct_and_control_fails(cpu_run, name):  # noqa: F811
    res = cpu_run(named(name), seconds=2.0, control=True)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"] or res["metrics"] == {}
    assert all(c["limit"] == 0 for c in res["checks"].values())
    assert any(v > 0 for v in res["control"].values()), res["control"]
    json.dumps(res)


def test_traced_run_reports_busy_and_window(cpu_run):  # noqa: F811
    res = cpu_run(named("iris-catchup"), seconds=1.0, trace=True)
    assert res["correct"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_unknown_data_kind_exits_naming_its_file(cpu_run):  # noqa: F811
    with pytest.raises(SystemExit, match="bench/data/nope.py"):
        cpu_run(named("iris-catchup", data={"kind": "nope"}))


def test_unknown_reference_exits_naming_its_file(cpu_run):  # noqa: F811
    with pytest.raises(SystemExit, match="bench/nope.py"):
        cpu_run(named("iris-catchup", reference="nope"))


@pytest.fixture
def spy_reference(monkeypatch):
    """A test-only reference module, ``bench.spy_reference``: the plain
    reference, recording each instance and its replays."""
    import jax.numpy as jnp

    from bench import reference

    made = []

    class Reference(reference.Reference):
        def __init__(self, conf, u_dtype=jnp.float32):
            super().__init__(conf, u_dtype)
            self.replays = 0
            made.append(self)

        def replay(self, *a, **k):
            self.replays += 1
            return super().replay(*a, **k)

    mod = types.ModuleType("bench.spy_reference")
    mod.Reference = Reference
    monkeypatch.setitem(sys.modules, "bench.spy_reference", mod)
    return made


def test_reference_named_by_the_configuration_is_used(  # noqa: F811
        cpu_run, spy_reference):
    import jax.numpy as jnp

    res = cpu_run(named("iris-catchup", reference="spy_reference"),
                  seconds=1.0, control=True)
    assert res["correct"], res["checks"]
    assert any(v > 0 for v in res["control"].values()), res["control"]
    assert [r.u_dtype for r in spy_reference] == [jnp.float32, jnp.bfloat16]
    assert [r.replays for r in spy_reference] == [1, 1]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"),
         "--workload", "iris-k4096-catchup", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
