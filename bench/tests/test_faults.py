"""The check catches a broken timed path: each fault the cells can have is
planted in the program underneath a full run, and ``correct`` comes out
false. (No cell's timed path exchanges anything between chips: tenants are
independent and every kernel runs per device, so there is no exchange to
leave out.)"""
import jax
import jax.numpy as jnp

from bench.tests.tiny import cpu_run, named  # noqa: F401


def _wrap_drain(monkeypatch, make):
    from repro.core import online

    orig = online._consume_many_replicated

    def broken(cfg, k, ss, rt, limit, keys, *, monitor=True):
        new, n, aux = orig(cfg, k, ss, rt, limit, keys, monitor=monitor)
        return make(ss, new), n, aux
    monkeypatch.setattr(online, "_consume_many_replicated", broken)


def test_step_that_returns_its_state_unchanged(cpu_run, monkeypatch):  # noqa: F811
    _wrap_drain(monkeypatch, lambda old, new: new._replace(tm=old.tm))
    res = cpu_run(named("iris-catchup"))
    assert not res["correct"]
    assert res["checks"]["bank_mismatch"]["value"] > 0


def test_half_of_the_tenants_left_out(cpu_run, monkeypatch):  # noqa: F811
    def half(old, new):
        R = old.tm.ta_state.shape[0]
        keep = (jnp.arange(R) >= R // 2)[:, None, None, None]
        return new._replace(tm=new.tm._replace(
            ta_state=jnp.where(keep, old.tm.ta_state, new.tm.ta_state)))
    _wrap_drain(monkeypatch, half)
    res = cpu_run(named("iris-catchup"))
    assert not res["correct"]
    assert res["checks"]["bank_mismatch"]["value"] > 0


def test_analysis_answer_altered(cpu_run, monkeypatch):  # noqa: F811
    from repro.core import accuracy

    orig = accuracy.analyze_replicated

    def altered(*a, **k):
        return jax.numpy.minimum(orig(*a, **k) + 1.0 / 64, 1.0)
    monkeypatch.setattr(accuracy, "analyze_replicated", altered)
    res = cpu_run(named("iris-catchup"))
    assert not res["correct"]
    assert res["checks"]["accuracy_mismatch"]["value"] > 0
