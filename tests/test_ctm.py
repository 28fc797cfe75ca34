"""The Convolutional Tsetlin machine (patch machines, DESIGN.md §18) against
its plain reference, ``bench/ctm_reference.py``, at a small size on the CPU:
12x12 images under a 4x4 window (81 patches of 16 + 8 + 8 features), 10
classes of 8 clauses, K=4 tenants.

Banks are seeded and random. States drawn uniformly in [1, 2N] include
about half of the literals, so such clauses fire on no patch (m = 0); a
bank also holds clauses that include a few literals (they fire on some
patches) and empty clauses (every patch while training, silent at
inference). Everything compared is an exact count or a bit: the two sides
must agree bit for bit.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import feedback as fb_mod
from repro.core import tm as tm_mod
from repro.core.tm import TMConfig, TMState, init_runtime
from repro.kernels import dispatch
from repro.serve import AdaptPolicy, ServiceConfig, TMService
from repro.serve.tunable import TunableConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import ctm_reference as ref  # noqa: E402

IMAGE, WINDOW = (12, 12), (4, 4)
C, J, N = 10, 8, 128
K = 4
S, T = 3.0, 4


def config(backend: str = "ref") -> TMConfig:
    return TMConfig(n_features=IMAGE[0] * IMAGE[1], max_classes=C,
                    max_clauses=J, n_states=N, image=IMAGE, window=WINDOW,
                    backend=backend)


def bank(rng, R: int, L: int) -> np.ndarray:
    """[R, C, J, L] int16: per clause, states uniform in [1, 2N], or a few
    literals included, or none."""
    shape = (R, C, J, L)
    uniform = rng.integers(1, 2 * N + 1, shape)
    sparse = np.where(rng.random(shape) < 2.0 / L,
                      rng.integers(N + 1, 2 * N + 1, shape),
                      rng.integers(1, N + 1, shape))
    kind = rng.integers(0, 3, (R, C, J, 1))
    ta = np.where(kind == 0, uniform, np.where(kind == 1, sparse, N))
    return ta.astype(np.int16)


def images(rng, n: int) -> np.ndarray:
    return rng.random((n, IMAGE[0] * IMAGE[1])) < 0.5


def ref_conf(threshold: float = 0.1, analyze_every: int = 4) -> dict:
    return {"machine": {"n_features": IMAGE[0] * IMAGE[1], "image": IMAGE,
                        "window": WINDOW, "max_classes": C, "max_clauses": J,
                        "n_states": N, "boost_true_positive": True},
            "service": {"chunk": 4, "analyze_every": analyze_every,
                        "rollback_threshold": threshold}}


def test_config_shapes_and_validation():
    cfg = config()
    assert cfg.patched and cfg.n_patches == 81
    assert cfg.clause_features == 16 + 8 + 8 and cfg.n_literals == 64
    assert cfg.state_dtype == jnp.int16
    wide = TMConfig(n_features=784, max_classes=10, max_clauses=2000,
                    n_states=128, image=[28, 28], window=[10, 10])
    assert wide.image == (28, 28) and hash(wide)
    assert (wide.n_patches, wide.n_literals) == (361, 272)
    with pytest.raises(ValueError, match="together"):
        TMConfig(n_features=144, max_classes=C, max_clauses=J, image=IMAGE)
    with pytest.raises(ValueError, match="pixels"):
        TMConfig(n_features=100, max_classes=C, max_clauses=J, image=IMAGE,
                 window=WINDOW)
    with pytest.raises(ValueError, match="fit"):
        TMConfig(n_features=144, max_classes=C, max_clauses=J, image=IMAGE,
                 window=(13, 4))
    assert not TMConfig(n_features=16, max_classes=3, max_clauses=4).patched


def test_patch_literals_equal_reference():
    xs = images(np.random.default_rng(1), 5)
    got = np.asarray(tm_mod.make_patch_literals(config(), jnp.asarray(xs)))
    assert got.shape == (5, 81, 64)
    for x, g in zip(xs, got):
        np.testing.assert_array_equal(
            g, np.asarray(ref.patch_literals(x, IMAGE, WINDOW)))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("training", [True, False])
def test_clause_outputs_and_chosen_patches_equal_reference(backend,
                                                           training):
    rng = np.random.default_rng(2)
    cfg = config(backend)
    ta = bank(rng, K, cfg.n_literals)
    xs = images(rng, 2 * 3).reshape(2, 3, -1)        # D = 2 streams, B = 3
    u = rng.random((2, 3, C, J)).astype(np.float32)
    lits = tm_mod.make_patch_literals(cfg, jnp.asarray(xs))
    out, chosen = dispatch.resolve(backend).patch_clause_eval_replicated(
        jnp.asarray(ta) > N, lits, jnp.asarray(u), training=training)
    ms = []
    for r in range(K):
        for b in range(3):
            plits = ref.patch_literals(xs[r % 2, b], IMAGE, WINDOW)
            o, fired = ref.patch_clauses(jnp.asarray(ta[r]), plits, N,
                                         training)
            np.testing.assert_array_equal(out[r, b], o)
            np.testing.assert_array_equal(
                chosen[r, b], ref.choose(fired, jnp.asarray(u[r % 2, b])))
            ms.append(np.asarray(fired.sum(0)))
    ms = np.stack(ms)
    # the banks hold clauses firing on no patch, on some and on all (the
    # empty ones, silent at inference)
    assert (ms == 0).any() and ((ms > 0) & (ms < 81)).any()
    assert (ms == 81).any()


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_predictions_equal_reference(backend):
    rng = np.random.default_rng(3)
    cfg = config(backend)
    ta = bank(rng, K, cfg.n_literals)
    xs = images(rng, 6)
    rt = init_runtime(cfg)
    got = np.asarray(tm_mod.predict_batch_replicated(
        cfg, TMState(ta_state=jnp.asarray(ta)), rt, jnp.asarray(xs)[None]))
    want = [[int(ref.predict(jnp.asarray(ta[r]), x, N, IMAGE, WINDOW))
             for x in xs] for r in range(K)]
    np.testing.assert_array_equal(got, want)
    # a single machine takes the same path
    one = tm_mod.predict_batch(cfg, TMState(ta_state=jnp.asarray(ta[0])), rt,
                               jnp.asarray(xs))
    np.testing.assert_array_equal(one, got[0])


def test_kernel_entries_ref_and_pallas_agree():
    rng = np.random.default_rng(4)
    cfg = config()
    kr, kp = dispatch.resolve("ref"), dispatch.resolve("pallas")
    L = cfg.n_literals
    ta = jnp.asarray(bank(rng, K, L))
    lits = tm_mod.make_patch_literals(cfg, jnp.asarray(images(rng, 4))
                                      .reshape(1, 4, -1))       # D = 1, B = 4
    u = jnp.asarray(rng.random((1, 4, C, J)), jnp.float32)
    for training in (True, False):
        a = kr.patch_clause_eval_replicated(ta > N, lits, u,
                                            training=training)
        b = kp.patch_clause_eval_replicated(ta > N, lits, u,
                                            training=training)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert kp.patch_clause_eval_replicated(
            ta > N, lits, training=training)[1] is None
    # feedback: per-clause literal rows, the uniforms shared by D = 2 streams
    lit_rows = jnp.asarray(rng.random((K, C, J, L)) < 0.5)
    masks = [jnp.asarray(rng.random((K, C, J)) < 0.5) for _ in range(3)]
    uf = jnp.asarray(rng.random((2, C, J, L)), jnp.float32)
    kw = dict(s=jnp.full((K,), S, jnp.float32), n_states=N,
              s_policy="standard", boost_true_positive=False)
    new_r = kr.feedback_patch_replicated(ta, lit_rows, *masks, uf, **kw)
    new_p = kp.feedback_patch_replicated(ta, lit_rows, *masks, uf, **kw)
    np.testing.assert_array_equal(new_r, new_p)
    assert (np.asarray(new_r) != np.asarray(ta)).any()
    # with every clause's row equal to its replica's, it is the plain entry
    row = jnp.asarray(rng.random((2, L)) < 0.5)
    same = jnp.broadcast_to(jnp.tile(row, (2, 1))[:, None, None],
                            (K, C, J, L))
    np.testing.assert_array_equal(
        kr.feedback_patch_replicated(ta, same, *masks, uf, **kw),
        kr.feedback_step_replicated(ta, row, *masks, uf, **kw))


def _service(backend: str, threshold: float, eval_xy, seed: int):
    rng = np.random.default_rng(5)
    cfg = config(backend)
    base = bank(rng, 1, cfg.n_literals)[0]
    return base, TMService(cfg, TMState(ta_state=jnp.asarray(base)),
                           ServiceConfig(
        replicas=K, buffer_capacity=8, chunk=4, ingress_block=4, packed=True,
        s=S, T=T, seed=seed,
        policy=AdaptPolicy(analyze_every=4, rollback_threshold=threshold),
    ), eval_x=eval_xy[0], eval_y=eval_xy[1])


@pytest.mark.parametrize("backend,threshold", [("ref", 0.1),
                                               ("ref", -1.0),
                                               ("pallas", -1.0)])
def test_two_drained_chunks_equal_reference(backend, threshold):
    """Two ticks of 4 rows a tenant through TMService (packed buffers), an
    analysis after each; a threshold of -1 rolls every tenant back at its
    second analysis, so the policy's restore is compared too."""
    rng = np.random.default_rng(6)
    eval_x, eval_y = images(rng, 6), rng.integers(0, C, 6)
    rows_x = images(rng, K * 8).reshape(K, 8, -1)
    rows_y = rng.integers(0, C, (K, 8))
    seed = 2**31 + 11
    base, svc = _service(backend, threshold, (eval_x, eval_y), seed)
    for i in range(8):
        assert svc.submit_rows(rows_x[:, i], rows_y[:, i]).all()
    reps = [svc.tick(), svc.tick()]
    keys = np.stack([np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                   r)) for r in range(K)])
    want = ref.Reference(ref_conf(threshold)).replay(
        base, keys, list(rows_x), list(rows_y),
        [(np.asarray(r.trained), r.accuracy is not None) for r in reps],
        eval_x, eval_y, S, T)
    assert all(np.array_equal(r.trained, [4] * K) for r in reps)
    np.testing.assert_array_equal(svc.ss.tm.ta_state, want["banks"])
    np.testing.assert_array_equal(svc.steps, want["steps"])
    np.testing.assert_array_equal(np.stack([r.accuracy for r in reps]),
                                  want["acc"])
    np.testing.assert_array_equal(svc.rollbacks, want["rollbacks"])
    assert (np.asarray(svc.rollbacks) > 0).all() == (threshold < 0)
    assert (want["banks"] != base).any()
    # serving packs the batch; a patch machine unpacks it into images
    np.testing.assert_array_equal(
        svc.serve(eval_x),
        [[int(ref.predict(jnp.asarray(want["banks"][r]), x, N, IMAGE, WINDOW))
          for x in eval_x] for r in range(K)])
    c = svc.obs.counters()
    assert c["patch.evals.train"] == 2 * K * 4 * 81
    assert c["patch.evals.analysis"] == 2 * K * 6 * 81
    assert c["patch.evals.monitor"] == 0
    assert c["feedback.grid_steps"] == 0      # the patch kernel is not counted


def test_bfloat16_uniforms_change_the_replay():
    rng = np.random.default_rng(7)
    base = bank(rng, 1, 64)[0]
    rows_x = images(rng, 2 * 8).reshape(2, 8, -1)
    rows_y = rng.integers(0, C, (2, 8))
    keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in (1, 2)])
    sched = [(np.array([4, 4]), False), (np.array([4, 4]), False)]
    args = (base, keys, list(rows_x), list(rows_y), sched,
            images(rng, 3), rng.integers(0, C, 3), S, T)
    f32 = ref.Reference(ref_conf()).replay(*args)
    bf16 = ref.Reference(ref_conf(), u_dtype=jnp.bfloat16).replay(*args)
    assert (f32["banks"] != bf16["banks"]).sum() > 0


def test_single_machine_update_is_the_fleet_of_one():
    rng = np.random.default_rng(8)
    cfg = config()
    ta = jnp.asarray(bank(rng, 1, cfg.n_literals)[0])
    rt = init_runtime(cfg, s=S, T=T)
    x = jnp.asarray(images(rng, 1)[0])
    key = jax.random.PRNGKey(9)
    one, _, _ = fb_mod.train_update(cfg, TMState(ta_state=ta), rt, x,
                                    jnp.int32(3), key)
    want = ref.update(ta, x, 3, key, jnp.float32(S), jnp.int32(T),
                      image=IMAGE, window=WINDOW, n_states=N, boost=True,
                      u_dtype=jnp.float32)
    np.testing.assert_array_equal(one.ta_state, want)


def test_patch_machines_refuse_what_they_do_not_support(tmp_path):
    cfg = config()
    st = TMState(ta_state=jnp.full((C, J, cfg.n_literals), N, jnp.int16))
    with pytest.raises(ValueError, match="patch machines"):
        TMService(cfg, st, ServiceConfig(replicas=4, resident=2))
    with pytest.raises(ValueError, match="patch machines"):
        TMService(cfg, st, ServiceConfig(replicas=4,
                                         tunable=TunableConfig()))
    svc = TMService(cfg, st, ServiceConfig(replicas=2))
    with pytest.raises(ValueError, match="patch machines"):
        svc.save(str(tmp_path))
    with pytest.raises(ValueError, match="patch machines"):
        tm_mod.predict_batch_pruned(cfg, st, init_runtime(cfg),
                                    jnp.zeros((1, 144), bool),
                                    jnp.zeros((C, 2), jnp.int32))
