"""Least-work counts against the hand figures, and the peaks table."""
import pytest

from bench import peaks, setup, work

# a booleanized 28x28 machine of 10 classes x 64 clauses with int8 banks:
# the hand figures at f=784
WIDE = {"machine": {"n_features": 784, "max_classes": 10, "max_clauses": 64,
                    "n_states": 63}}


def test_training_bank_bytes_per_trained_tenant():
    assert work.bank_bytes_per_trained_tenant(WIDE) == 2_007_040
    assert work.bank_bytes_per_trained_tenant(
        setup.load_config("tm-iris-paper-k4096")) == 3_072


def test_train_bytes_and_least_time():
    assert work.row_bytes(WIDE) == 100
    n = work.train_bytes(WIDE, tenant_ticks=512, rows=8192)
    assert n == 512 * 2_007_040 + 8192 * 100
    p = peaks.peaks("TPU v5 lite")
    assert work.least_seconds(819e9, p) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        peaks.peaks("some other chip")
