"""chip_smoke.py off the chip: it refuses to run without a TPU or outside a
checkout, and its phases run end to end at a tiny width with the Pallas
kernels interpreted (the chip run itself is ``python chip_smoke.py``)."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_device_check_exits_nonzero_without_tpu():
    r = _run(SCRIPT, ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "JAX found no TPU" in r.stderr


def test_script_alone_exits_nonzero(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    r = _run(str(lone), tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_one_chip_phases_interpreted_at_tiny_width(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    for name, value in dict(N_TRAIN=128, N_TEST=64, EPOCHS=2,
                            ROUNDS=2).items():
        monkeypatch.setattr(cs, name, value)

    from repro.configs import tm_mnist

    side = 7
    cfg = dataclasses.replace(tm_mnist.config_for_side(side).tm,
                              backend="pallas")
    # bitwise against ref and the learning bar are checked inside; a failed
    # phase raises SystemExit
    hlo = cs.one_chip(cfg, cs.make_data(side), n_tenants=4)
    assert "tpu_custom_call" not in hlo   # interpreted off the chip
