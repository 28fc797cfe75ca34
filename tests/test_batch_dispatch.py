"""Batch-first kernel path + backend dispatch layer.

The batch contract (DESIGN.md §8): ``clause_eval_batch_replicated`` at one
machine (R = D = 1, as the core's single-machine functions call it) must
equal stacking the per-sample kernel over rows bit-for-bit, on every
backend, for every shape — including the awkward ones (B=1, B=257, L not a
multiple of the 128-lane tile).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    TMConfig, init_runtime, init_state, predict, predict_batch,
)
from repro.core import accuracy as acc_mod
from repro.core import online as online_mod
from repro.core import tm as tm_mod
from repro.kernels import dispatch, ref

# (C, J, L, B) — odd shapes on purpose: batch of 1, batch over the lane
# count (257), literal axes that straddle the 128-lane tile boundary.
BATCH_SHAPES = [
    (1, 2, 5, 1),
    (3, 16, 32, 7),
    (2, 6, 17, 257),
    (4, 33, 129, 33),
    (3, 16, 200, 128),
]


def _batch_of_one(kb, include, lits, training):
    """The batch entry at one machine: [C, J, L] x [B, L] -> [B, C, J]."""
    return kb.clause_eval_batch_replicated(
        include[None], lits[None], training=training)[0]


def _rand_case(shape, seed=None):
    C, J, L, B = shape
    rng = np.random.default_rng(seed if seed is not None else hash(shape) % 2**31)
    include = jnp.asarray(rng.random((C, J, L)) < 0.3)
    lits = jnp.asarray(rng.random((B, L)) < 0.5)
    return include, lits


@pytest.mark.parametrize("shape", BATCH_SHAPES)
@pytest.mark.parametrize("training", [True, False])
def test_clause_eval_batch_matches_per_sample_loop(shape, training):
    include, lits = _rand_case(shape)
    want = ref.clause_eval_loop(include, lits, training=training)
    for backend in ("ref", "pallas"):
        kb = dispatch.resolve(backend)
        got = _batch_of_one(kb, include, lits, training)
        np.testing.assert_array_equal(
            np.asarray(want), np.asarray(got), err_msg=f"backend={backend}"
        )


@pytest.mark.parametrize("shape", BATCH_SHAPES[:3])
def test_clause_eval_batch_ref_pallas_bit_parity(shape):
    include, lits = _rand_case(shape, seed=11)
    for training in (True, False):
        a = _batch_of_one(dispatch.resolve("ref"), include, lits, training)
        b = _batch_of_one(dispatch.resolve("pallas"), include, lits, training)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_clause_eval_batch_empty_clause_convention():
    include = jnp.zeros((2, 4, 32), dtype=bool)  # every clause empty
    lits = jnp.asarray(np.random.default_rng(0).random((5, 32)) < 0.5)
    for backend in ("ref", "pallas"):
        kb = dispatch.resolve(backend)
        assert bool(jnp.all(_batch_of_one(kb, include, lits, True)))
        assert not bool(jnp.any(_batch_of_one(kb, include, lits, False)))


def test_dispatch_registry_names_and_auto():
    assert set(dispatch.available()) >= {"ref", "pallas", "auto"}
    assert dispatch.resolve("ref").name == "ref"
    assert dispatch.resolve("pallas").name == "pallas"
    # auto means pallas on TPU; elsewhere TM_BACKEND (the CI kernel-parity
    # job) may override the ref default.
    expect = ("pallas" if jax.default_backend() == "tpu"
              else os.environ.get("TM_BACKEND") or "ref")
    assert dispatch.resolve("auto").name == expect
    # interpret mode follows the platform, decided once by resolve
    assert dispatch.resolve("pallas").interpret == (
        jax.default_backend() != "tpu")
    assert not dispatch.resolve("ref").interpret
    with pytest.raises(ValueError):
        dispatch.resolve("no-such-backend")


def test_dispatch_register_custom_backend():
    calls = {"n": 0}

    def factory():
        base = dispatch.resolve("ref")
        calls["n"] += 1
        return base._replace(name="custom")

    dispatch.register("custom", factory)
    try:
        assert dispatch.resolve("custom").name == "custom"
        dispatch.resolve("custom")
        assert calls["n"] == 1  # factory result is cached
        cfg = TMConfig(n_features=4, max_classes=2, max_clauses=4,
                       backend="custom")
        assert cfg.backend == "custom"
    finally:
        dispatch._FACTORIES.pop("custom", None)
        dispatch._CACHE.pop("custom", None)


def test_config_rejects_unknown_backend_accepts_auto():
    with pytest.raises(ValueError):
        TMConfig(n_features=4, max_classes=2, max_clauses=4, backend="nope")
    cfg = TMConfig(n_features=4, max_classes=2, max_clauses=4, backend="auto")
    assert cfg.backend == "auto"


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_predict_batch_bitwise_matches_vmap_of_predict(backend):
    """The acceptance contract: batch-first serving == per-sample serving."""
    from repro.data import iris

    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=50,
                   backend=backend)
    st = init_state(cfg, jax.random.PRNGKey(2))
    rt = init_runtime(cfg)
    xs, _ = iris.load()
    xs = jnp.asarray(xs)
    batched = predict_batch(cfg, st, rt, xs)
    vmapped = jax.vmap(lambda x: predict(cfg, st, rt, x))(xs)
    np.testing.assert_array_equal(np.asarray(batched), np.asarray(vmapped))


def test_analyze_matches_per_sample_predictions():
    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=50)
    st = init_state(cfg, jax.random.PRNGKey(3))
    rt = init_runtime(cfg)
    rng = np.random.default_rng(4)
    xs = jnp.asarray(rng.random((40, 16)) < 0.5)
    ys = jnp.asarray(rng.integers(0, 3, 40), dtype=jnp.int32)
    valid = jnp.asarray(rng.random(40) < 0.8)
    preds = jax.vmap(lambda x: predict(cfg, st, rt, x))(xs)
    ok = (np.asarray(preds) == np.asarray(ys)) & np.asarray(valid)
    want = ok.sum() / max(np.asarray(valid).sum(), 1)
    got = float(acc_mod.analyze(cfg, st, rt, xs, ys, valid))
    assert abs(got - want) < 1e-6


def _fleet_of_one(tree):
    return jax.tree.map(lambda a: jnp.asarray(a)[None], tree)


def test_consume_many_matches_serial_updates():
    """The drain at one machine (_consume_many_replicated, R = 1) == a hand
    loop of train_update over the same keys."""
    from repro.core import feedback as fb_mod
    from repro.data import buffer as buf_mod
    from repro.data import iris

    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=50)
    st = init_state(cfg, jax.random.PRNGKey(5))
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    K = 8
    buf = buf_mod.make(16, cfg.n_features)
    for i in range(K):
        buf, ok = buf_mod.push(
            buf, jnp.asarray(xs[i], dtype=bool), jnp.int32(ys[i])
        )
        assert bool(ok)
    ss = _fleet_of_one(
        online_mod.SessionState(tm=st, buf=buf, step=jnp.int32(0)))

    key = jax.random.PRNGKey(9)
    out, n, aux = online_mod._consume_many_replicated(
        cfg, K, ss, rt, jnp.full((1,), K, jnp.int32), key[None])
    assert n.shape == (1,) and int(n[0]) == K and int(out.buf.size[0]) == 0

    ref_tm = st
    for i, kk in enumerate(jax.random.split(key, K)):
        ref_tm, _, _ = fb_mod.train_update(
            cfg, ref_tm, rt, jnp.asarray(xs[i], dtype=bool),
            jnp.int32(ys[i]), kk
        )
    np.testing.assert_array_equal(
        np.asarray(out.tm.ta_state[0]), np.asarray(ref_tm.ta_state)
    )
    assert aux.valid.shape == (1, K) and bool(jnp.all(aux.valid))


def test_consume_many_respects_limit_and_empty_buffer():
    from repro.data import buffer as buf_mod
    from repro.data import iris

    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=50)
    st = init_state(cfg, jax.random.PRNGKey(6))
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    buf = buf_mod.make(16, cfg.n_features)
    for i in range(5):
        buf, _ = buf_mod.push(
            buf, jnp.asarray(xs[i], dtype=bool), jnp.int32(ys[i])
        )
    ss = _fleet_of_one(
        online_mod.SessionState(tm=st, buf=buf, step=jnp.int32(0)))
    key = jax.random.PRNGKey(10)[None]

    # limit < buffered: stops at the limit, leaves the rest buffered
    out, n, _ = online_mod._consume_many_replicated(
        cfg, 8, ss, rt, jnp.full((1,), 3, jnp.int32), key)
    assert int(n[0]) == 3 and int(out.buf.size[0]) == 2
    # chunk > buffered: consumes what exists, TM state untouched afterwards
    out2, n2, aux2 = online_mod._consume_many_replicated(
        cfg, 8, out, rt, jnp.full((1,), 8, jnp.int32), key
    )
    assert int(n2[0]) == 2 and int(out2.buf.size[0]) == 0
    assert not bool(jnp.any(aux2.valid[0, 2:]))


def test_online_session_chunked_learn_counts():
    from repro.data import iris

    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=50)
    sess = online_mod.OnlineSession(
        cfg, init_state(cfg), init_runtime(cfg, s=3.0, T=15),
        buffer_capacity=64, chunk=8,
    )
    xs, ys = iris.load()
    for i in range(20):
        assert sess.offer(xs[i], int(ys[i]))
    assert sess.learn_available(13) == 13      # crosses a partial chunk
    assert sess.buffered == 7
    assert sess.learn_available(100) == 7      # drains to empty
    assert sess.learn_available(4) == 0        # empty buffer trains nothing
    assert int(sess.ss.step) == 20


def test_tm_online_adapt_manager_serves_and_rolls_back():
    from repro.data import iris
    from repro.serve.online_adapt import TMOnlineAdaptConfig, TMOnlineAdaptManager

    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=50)
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    mgr = TMOnlineAdaptManager(
        cfg, init_state(cfg), rt, xs[100:], ys[100:],
        TMOnlineAdaptConfig(analyze_every=16, rollback_threshold=0.05,
                            chunk=8),
    )
    base = mgr.offline_train(xs[:100], ys[:100], n_epochs=5)
    assert 0.0 <= base <= 1.0
    preds = mgr.serve(xs[:10])
    assert preds.shape == (10,)
    # Poisoned labels: shuffled ys force degradation -> rollback fires.
    rng = np.random.default_rng(0)
    for i in range(200):
        j = i % 100
        mgr.observe(xs[j], int(rng.integers(0, 3)))
        if mgr.rollbacks:
            break
    assert mgr.rollbacks >= 1
    assert len(mgr.history) >= 2


def test_forward_batch_matches_forward_rows():
    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=50)
    st = init_state(cfg, jax.random.PRNGKey(8))
    rt = init_runtime(cfg, n_active_clauses=8)
    rng = np.random.default_rng(12)
    xs = jnp.asarray(rng.random((9, 16)) < 0.5)
    for training in (True, False):
        cl_b, votes_b = tm_mod.forward_batch(cfg, st, rt, xs, training=training)
        for i in range(9):
            cl, votes = tm_mod.forward(cfg, st, rt, xs[i], training=training)
            np.testing.assert_array_equal(np.asarray(cl_b[i]), np.asarray(cl))
            np.testing.assert_array_equal(
                np.asarray(votes_b[i]), np.asarray(votes)
            )
