"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Shape/dtype sweeps + bit-exactness, per the kernel contract in DESIGN.md §8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, packing, ref

SHAPES = [
    (1, 2, 5),      # degenerate
    (3, 16, 32),    # the paper's iris machine
    (2, 6, 17),     # non-aligned everything
    (3, 8, 31),     # one under the packed-word boundary (tail masking)
    (3, 8, 33),     # one over it (multi-word + tail)
    (10, 100, 200), # MNIST-ish TM
    (4, 33, 129),   # one over tile boundaries
    (2, 6, 513),    # one over the BLK_L literal-block boundary — exercises
                    # the multi-block accumulation path in tier-1
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("training", [True, False])
def test_clause_eval_matches_ref(shape, training):
    C, J, L = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    include = jnp.asarray(rng.random((C, J, L)) < 0.3)
    lits = jnp.asarray(rng.random((L,)) < 0.5)
    want = ref.clause_eval(include, lits, training=training)
    got = dispatch.resolve("pallas").clause_eval(include, lits,
                                                 training=training)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_clause_eval_all_excluded_is_empty():
    include = jnp.zeros((2, 4, 32), dtype=bool)
    lits = jnp.ones((32,), dtype=bool)
    kb = dispatch.resolve("pallas")
    assert bool(jnp.all(kb.clause_eval(include, lits, training=True)))
    assert not bool(jnp.any(kb.clause_eval(include, lits, training=False)))


def test_clause_eval_single_violation_kills_clause():
    L = 32
    include = jnp.zeros((1, 2, L), dtype=bool).at[0, 0, 7].set(True)
    lits = jnp.ones((L,), dtype=bool).at[7].set(False)
    out = dispatch.resolve("pallas").clause_eval(include, lits, training=True)
    assert not bool(out[0, 0])  # included literal is 0 -> clause 0
    assert bool(out[0, 1])      # empty clause in training -> 1


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("policy", ["standard", "hardware"])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int16])
def test_feedback_matches_ref(shape, policy, dtype):
    C, J, L = shape
    n_states = 50 if dtype == jnp.int8 else 5000
    rng = np.random.default_rng(hash((shape, policy)) % 2**31)
    ta = jnp.asarray(rng.integers(1, 2 * n_states + 1, (C, J, L)), dtype=dtype)
    lits = jnp.asarray(rng.random((L,)) < 0.5)
    c_out = jnp.asarray(rng.random((C, J)) < 0.5)
    t1 = jnp.asarray(rng.random((C, J)) < 0.5)
    t2 = jnp.asarray(rng.random((C, J)) < 0.3) & ~t1
    u = jnp.asarray(rng.random((C, J, L)), dtype=jnp.float32)
    for boost in (True, False):
        kw = dict(s=jnp.float32(1.375), n_states=n_states, s_policy=policy,
                  boost_true_positive=boost)
        want = ref.feedback_step(ta, lits, c_out, t1, t2, u, **kw)
        got = dispatch.resolve("pallas").feedback_step(
            ta, lits, c_out, t1, t2, u, **kw)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_feedback_states_stay_in_bounds():
    C, J, L, n = 2, 8, 32, 10
    rng = np.random.default_rng(3)
    ta = jnp.asarray(rng.integers(1, 2 * n + 1, (C, J, L)), dtype=jnp.int8)
    lits = jnp.ones((L,), dtype=bool)
    ones = jnp.ones((C, J), dtype=bool)
    u = jnp.zeros((C, J, L), dtype=jnp.float32)  # every draw fires
    out = dispatch.resolve("pallas").feedback_step(
        ta, lits, ones, ones, jnp.zeros_like(ones), u,
        s=jnp.float32(1.0), n_states=n, s_policy="standard",
        boost_true_positive=True,
    )
    o = np.asarray(out)
    assert o.min() >= 1 and o.max() <= 2 * n


# Packed-kernel parity (DESIGN.md §13). The packed kernels are layout-
# agnostic — any include/literal pair packed with the SAME word layout and
# zero include tails works — so here the literal axis packs contiguously
# (pack_bits over L), exercising tail masking at L = 31/33 and multi-word
# accumulation at L = 513 directly against the unpacked oracle.


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_clause_eval_batch_packed_matches_unpacked_oracle(shape, mod):
    mod = dispatch.resolve(mod)
    C, J, L = shape
    rng = np.random.default_rng(hash(("packed",) + shape) % 2**31)
    include = jnp.asarray(rng.random((C, J, L)) < 0.3)
    lits = jnp.asarray(rng.random((9, L)) < 0.5)
    inc_p = packing.pack_bits(include)
    lit_p = packing.pack_bits(lits)
    for training in (True, False):
        want = ref.clause_eval_batch(include, lits, training=training)
        got = mod.clause_eval_batch_packed(inc_p, lit_p, training=training)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


# Replica-parallel shapes: (R, D, C, J, L) with odd sizes that straddle the
# int8 32x128 tile boundaries, plus grid-sharing layouts (D < R).
REP_SHAPES = [
    (1, 1, 1, 2, 5),       # degenerate single replica
    (3, 1, 2, 6, 17),      # one data stream shared by 3 grid cells
    (6, 3, 3, 16, 32),     # the iris machine, 2x3 grid-over-orderings
    (2, 2, 2, 8, 31),      # one under the packed-word boundary
    (5, 5, 2, 7, 33),      # replicas == data streams (system path), odd L
    (4, 2, 4, 33, 129),    # one over both tile boundaries
    (4, 2, 2, 6, 513),     # one over the BLK_L literal-block boundary
]


def _rep_inputs(R, D, C, J, L, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "include": jnp.asarray(rng.random((R, C, J, L)) < 0.3),
        "lits": jnp.asarray(rng.random((D, L)) < 0.5),
        "rng": rng,
    }


@pytest.mark.parametrize("shape", REP_SHAPES)
@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_clause_eval_replicated_matches_stacked(shape, mod):
    mod = dispatch.resolve(mod)
    R, D, C, J, L = shape
    inp = _rep_inputs(*shape, seed=hash(shape) % 2**31)
    for training in (True, False):
        want = jnp.stack([
            ref.clause_eval(inp["include"][r], inp["lits"][r % D],
                            training=training)
            for r in range(R)
        ])
        got = mod.clause_eval_replicated(
            inp["include"], inp["lits"], training=training
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("shape", REP_SHAPES)
@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_clause_eval_batch_replicated_matches_stacked(shape, mod):
    mod = dispatch.resolve(mod)
    R, D, C, J, L = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    include = jnp.asarray(rng.random((R, C, J, L)) < 0.3)
    lits = jnp.asarray(rng.random((D, 5, L)) < 0.5)
    for training in (True, False):
        want = jnp.stack([
            ref.clause_eval_batch(include[r], lits[r % D], training=training)
            for r in range(R)
        ])
        got = mod.clause_eval_batch_replicated(include, lits, training=training)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("shape", REP_SHAPES)
@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_clause_eval_batch_replicated_packed_matches_unpacked(shape, mod):
    mod = dispatch.resolve(mod)
    R, D, C, J, L = shape
    rng = np.random.default_rng(hash(("packed",) + shape) % 2**31)
    include = jnp.asarray(rng.random((R, C, J, L)) < 0.3)
    lits = jnp.asarray(rng.random((D, 5, L)) < 0.5)
    inc_p = packing.pack_bits(include)
    lit_p = packing.pack_bits(lits)
    for training in (True, False):
        want = ref.clause_eval_batch_replicated(
            include, lits, training=training
        )
        got = mod.clause_eval_batch_replicated_packed(
            inc_p, lit_p, training=training
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("shape", REP_SHAPES)
@pytest.mark.parametrize("policy", ["standard", "hardware"])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int16])
@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_feedback_replicated_matches_stacked(shape, policy, dtype, mod):
    """feedback_step_replicated == stacking per-replica feedback_step calls,
    bit for bit, on both backends (pallas in interpret mode)."""
    mod = dispatch.resolve(mod)
    R, D, C, J, L = shape
    n_states = 50 if dtype == jnp.int8 else 5000
    rng = np.random.default_rng(hash((shape, policy)) % 2**31)
    ta = jnp.asarray(rng.integers(1, 2 * n_states + 1, (R, C, J, L)), dtype=dtype)
    lits = jnp.asarray(rng.random((D, L)) < 0.5)
    c_out = jnp.asarray(rng.random((R, C, J)) < 0.5)
    t1 = jnp.asarray(rng.random((R, C, J)) < 0.5)
    t2 = jnp.asarray(rng.random((R, C, J)) < 0.3) & ~t1
    u = jnp.asarray(rng.random((D, C, J, L)), dtype=jnp.float32)
    s = jnp.asarray(1.0 + 5.0 * rng.random(R), dtype=jnp.float32)
    for boost in (True, False):
        kw = dict(n_states=n_states, s_policy=policy, boost_true_positive=boost)
        want = jnp.stack([
            ref.feedback_step(ta[r], lits[r % D], c_out[r], t1[r], t2[r],
                              u[r % D], s=s[r], **kw)
            for r in range(R)
        ])
        got = mod.feedback_step_replicated(
            ta, lits, c_out, t1, t2, u, s=s, **kw
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_feedback_replicated_rejects_bad_data_axis():
    ta = jnp.ones((4, 1, 2, 8), dtype=jnp.int8)
    lits = jnp.zeros((3, 8), dtype=bool)  # 3 does not divide 4
    with pytest.raises(ValueError, match="must divide"):
        ref.feedback_step_replicated(
            ta, lits, jnp.zeros((4, 1, 2), bool), jnp.zeros((4, 1, 2), bool),
            jnp.zeros((4, 1, 2), bool), jnp.zeros((3, 1, 2, 8), jnp.float32),
            s=jnp.ones(4), n_states=3, s_policy="standard",
            boost_true_positive=True,
        )


def test_end_to_end_backend_parity():
    """Full TM training is bit-exact between ref and pallas backends."""
    from repro.core import TMConfig, init_runtime, init_state, train_epochs
    from repro.data import iris

    xs, ys = iris.load()
    key = jax.random.PRNGKey(7)
    outs = {}
    for backend in ("ref", "pallas"):
        cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16,
                       n_states=50, backend=backend)
        rt = init_runtime(cfg, s=1.375, T=15)
        st = train_epochs(cfg, init_state(cfg), rt,
                          jnp.asarray(xs[:30]), jnp.asarray(ys[:30]), key, 2)
        outs[backend] = np.asarray(st.ta_state)
    np.testing.assert_array_equal(outs["ref"], outs["pallas"])
