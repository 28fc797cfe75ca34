"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="optional dev dependency (requirements-dev.txt)"
)
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import TMConfig, init_runtime, init_state, train_step
from repro.core import tm as tm_mod
from repro.kernels import dispatch, ref

_shapes = st.tuples(
    st.integers(1, 4),    # classes
    st.integers(1, 10).map(lambda j: 2 * j),  # clauses (even)
    st.integers(1, 40),   # literals
)


@settings(max_examples=25, deadline=None)
@given(shape=_shapes, seed=st.integers(0, 2**31 - 1), training=st.booleans())
def test_kernel_clause_eval_equals_oracle(shape, seed, training):
    C, J, L = shape
    rng = np.random.default_rng(seed)
    include = jnp.asarray(rng.random((C, J, L)) < rng.random())
    lits = jnp.asarray(rng.random((L,)) < 0.5)
    want = ref.clause_eval(include, lits, training=training)
    got = dispatch.resolve("pallas").clause_eval_replicated(
        include[None], lits[None], training=training)[0]
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@settings(max_examples=25, deadline=None)
@given(
    shape=_shapes,
    seed=st.integers(0, 2**31 - 1),
    s=st.floats(1.0, 10.0),
    policy=st.sampled_from(["standard", "hardware"]),
)
def test_kernel_feedback_equals_oracle_and_bounds(shape, seed, s, policy):
    C, J, L = shape
    n = 50
    rng = np.random.default_rng(seed)
    ta = jnp.asarray(rng.integers(1, 2 * n + 1, (C, J, L)), dtype=jnp.int8)
    lits = jnp.asarray(rng.random((L,)) < 0.5)
    c_out = jnp.asarray(rng.random((C, J)) < 0.5)
    t1 = jnp.asarray(rng.random((C, J)) < 0.5)
    t2 = jnp.asarray(rng.random((C, J)) < 0.5) & ~t1
    u = jnp.asarray(rng.random((C, J, L)), dtype=jnp.float32)
    kw = dict(n_states=n, s_policy=policy, boost_true_positive=bool(seed % 2))
    want = np.asarray(ref.feedback_step(ta, lits, c_out, t1, t2, u,
                                        s=jnp.float32(s), **kw))
    # the fused kernel at one machine (R = D = 1)
    got = np.asarray(dispatch.resolve("pallas").feedback_step_replicated(
        ta[None], lits[None], c_out[None], t1[None], t2[None], u[None],
        s=jnp.full((1,), s, jnp.float32), **kw)[0])
    np.testing.assert_array_equal(want, got)
    # Invariants: states in [1, 2N]; |delta| <= 1 per TA per step.
    assert want.min() >= 1 and want.max() <= 2 * n
    assert np.abs(want.astype(int) - np.asarray(ta, dtype=int)).max() <= 1


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_train_step_invariants(seed):
    """After any train step: state bounds hold; votes bounded by clause count."""
    cfg = TMConfig(n_features=8, max_classes=3, max_clauses=8, n_states=20)
    rng = np.random.default_rng(seed)
    st0 = init_state(cfg, jax.random.PRNGKey(seed % 997))
    rt = init_runtime(cfg, s=1.0 + 5 * rng.random(), T=int(rng.integers(1, 20)))
    x = jnp.asarray(rng.random(8) < 0.5)
    y = jnp.int32(rng.integers(0, 3))
    st1, aux = train_step(cfg, st0, rt, x, y, jax.random.PRNGKey(seed % 991))
    v = np.asarray(st1.ta_state)
    assert v.min() >= 1 and v.max() <= 2 * cfg.n_states
    assert np.abs(np.asarray(aux.votes)).max() <= cfg.max_clauses // 2
    assert 0.0 <= float(aux.activity) <= 1.0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), frac=st.floats(0.05, 0.5))
def test_fault_masks_force_clause_eval(seed, frac):
    """Stuck-at-0 on ALL TAs of a clause makes it empty regardless of state."""
    from repro.core import faults as faults_mod

    cfg = TMConfig(n_features=8, max_classes=2, max_clauses=4, n_states=20)
    st0 = init_state(cfg, jax.random.PRNGKey(seed % 1013))
    rt = init_runtime(cfg)
    and_m = np.ones((2, 4, 16), dtype=bool)
    and_m[0, 0, :] = False  # kill every TA of clause (0, 0)
    rt = faults_mod.inject(rt, and_m, np.zeros_like(and_m))
    acts = tm_mod.ta_actions(cfg, st0, rt)
    assert not bool(jnp.any(acts[0, 0]))
    x = jnp.asarray(np.random.default_rng(seed).random(8) < 0.5)
    cl = tm_mod.eval_clauses(cfg, acts, tm_mod.make_literals(x), rt, training=False)
    assert not bool(cl[0, 0])  # empty clause at inference votes 0


@settings(max_examples=10, deadline=None)
@given(
    cap=st.integers(1, 8),
    ops_seq=st.lists(st.tuples(st.booleans(), st.integers(0, 99)), max_size=30),
)
def test_ring_buffer_model(cap, ops_seq):
    """Ring buffer behaves exactly like a bounded FIFO (model-based test)."""
    from collections import deque

    from repro.data import buffer

    buf = buffer.make(cap, 2)
    model: deque = deque()
    for is_push, val in ops_seq:
        if is_push:
            buf, ok = buffer.push(
                buf, jnp.asarray([val % 2, 1], dtype=bool), jnp.int32(val)
            )
            assert bool(ok) == (len(model) < cap)
            if len(model) < cap:
                model.append(val)
        else:
            buf, x, y, valid = buffer.pop(buf)
            assert bool(valid) == (len(model) > 0)
            if model:
                assert int(y) == model.popleft()
        assert int(buf.size) == len(model)


@settings(max_examples=20, deadline=None)
@given(
    cap=st.integers(1, 10),
    vals=st.lists(st.integers(0, 999), min_size=0, max_size=40),
    extra_pops=st.integers(0, 5),
)
def test_ring_buffer_fifo_capacity_and_empty_pop(cap, vals, extra_pops):
    """RingBuffer invariants: FIFO order preserved, size never exceeds
    capacity, pop-on-empty is a no-op flagged by nonempty=False."""
    from repro.data import buffer

    buf = buffer.make(cap, 3)
    accepted = []
    for v in vals:
        buf, ok = buffer.push(
            buf, jnp.asarray([v % 2, (v >> 1) % 2, 1], dtype=bool), jnp.int32(v)
        )
        if bool(ok):
            accepted.append(v)
        assert 0 <= int(buf.size) <= cap  # size never exceeds capacity

    popped = []
    for _ in range(len(accepted) + extra_pops):
        before = jax.tree.map(np.asarray, buf)
        buf, x, y, nonempty = buffer.pop(buf)
        if bool(nonempty):
            popped.append(int(y))
        else:
            # pop-on-empty: flagged, and the buffer state is untouched
            after = jax.tree.map(np.asarray, buf)
            for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
                np.testing.assert_array_equal(a, b)
    assert popped == accepted  # FIFO order, accepted rows only
    assert int(buf.size) == 0


@settings(max_examples=20, deadline=None)
@given(
    cap=st.integers(1, 10),
    head=st.integers(0, 9),
    size=st.integers(0, 10),
    block=st.integers(1, 10),
    count=st.integers(0, 10),
    seed=st.integers(0, 2**31 - 1),
)
def test_ring_push_block_equals_pushes(cap, head, size, block, count, seed):
    """``push_block`` of a staged block's first ``count`` rows leaves the
    ring, and reports the rows accepted, bitwise as ``count`` ``push``
    calls would."""
    from repro.data import buffer

    head, size = head % cap, min(size, cap)
    block, count = min(block, cap), min(count, block)
    rng = np.random.default_rng(seed)
    buf = buffer.make(cap, 3)._replace(
        data_x=jnp.asarray(rng.random((cap, 3)) < 0.5),
        data_y=jnp.asarray(rng.integers(0, 99, cap, dtype=np.int32)),
        head=jnp.int32(head), size=jnp.int32(size),
    )
    xs = jnp.asarray(rng.random((block, 3)) < 0.5)
    ys = jnp.asarray(rng.integers(0, 99, block, dtype=np.int32))
    got, n = buffer.push_block(buf, xs, ys, jnp.int32(count))
    want, accepted = buf, 0
    for i in range(count):
        want, ok = buffer.push(want, xs[i], ys[i])
        accepted += int(ok)
    assert int(n) == accepted
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=15, deadline=None)
@given(
    block_len=st.integers(1, 8),
    blocks_split=st.tuples(
        st.integers(1, 3), st.integers(1, 3), st.integers(1, 3)
    ),
    n_orderings=st.integers(1, 12),
    seed=st.integers(0, 2**31 - 1),
)
def test_block_orderings_partition_dataset(
    block_len, blocks_split, n_orderings, seed
):
    """Every ordering partitions the dataset exactly; set sizes match
    BlockSpec.sizes()."""
    from repro.data import blocks

    a, b, c = blocks_split
    spec = blocks.BlockSpec(
        block_len=block_len, offline_blocks=a,
        validation_blocks=b, online_blocks=c,
    )
    n = spec.n_blocks * block_len
    rng = np.random.default_rng(seed)
    xs = rng.random((n, 4)) < 0.5
    ys = np.arange(n, dtype=np.int32)  # unique labels -> exact partition check

    orderings = blocks.select_orderings(spec.n_blocks, n_orderings, seed=seed)
    sets = blocks.make_sets(xs, ys, spec, orderings)

    assert sets.offline_y.shape[1:] == (spec.sizes()[0],)
    assert sets.validation_y.shape[1:] == (spec.sizes()[1],)
    assert sets.online_y.shape[1:] == (spec.sizes()[2],)
    for o in range(len(orderings)):
        labels = np.concatenate(
            [sets.offline_y[o], sets.validation_y[o], sets.online_y[o]]
        )
        # exactly the original rows, each exactly once
        np.testing.assert_array_equal(np.sort(labels), ys)
        # and x rows ride along with their labels
        rows = np.concatenate(
            [sets.offline_x[o], sets.validation_x[o], sets.online_x[o]]
        )
        np.testing.assert_array_equal(rows[np.argsort(labels)], xs)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    shape=st.tuples(
        st.integers(1, 3),                        # H (grid cells per stream)
        st.integers(1, 3),                        # D (data streams)
        st.integers(1, 3),                        # classes
        st.integers(1, 6).map(lambda j: 2 * j),   # clauses (even)
        st.integers(1, 40),                       # literals
    ),
    policy=st.sampled_from(["standard", "hardware"]),
)
def test_kernel_feedback_replicated_equals_stacked_oracle(seed, shape, policy):
    """Property form of the replica parity contract: for any R = H*D layout,
    feedback_step_replicated == stacked per-replica feedback_step, bitwise,
    on both backends."""
    H, D, C, J, L = shape
    R = H * D
    n = 50
    rng = np.random.default_rng(seed)
    ta = jnp.asarray(rng.integers(1, 2 * n + 1, (R, C, J, L)), dtype=jnp.int8)
    lits = jnp.asarray(rng.random((D, L)) < 0.5)
    c_out = jnp.asarray(rng.random((R, C, J)) < 0.5)
    t1 = jnp.asarray(rng.random((R, C, J)) < 0.5)
    t2 = jnp.asarray(rng.random((R, C, J)) < 0.5) & ~t1
    u = jnp.asarray(rng.random((D, C, J, L)), dtype=jnp.float32)
    s = jnp.asarray(1.0 + 5.0 * rng.random(R), dtype=jnp.float32)
    kw = dict(n_states=n, s_policy=policy, boost_true_positive=bool(seed % 2))
    want = np.stack([
        np.asarray(ref.feedback_step(
            ta[r], lits[r % D], c_out[r], t1[r], t2[r], u[r % D], s=s[r], **kw
        ))
        for r in range(R)
    ])
    for mod in (ref, dispatch.resolve("pallas")):
        got = np.asarray(mod.feedback_step_replicated(
            ta, lits, c_out, t1, t2, u, s=s, **kw
        ))
        np.testing.assert_array_equal(want, got)
    # Invariants survive replication: states in [1, 2N], |delta| <= 1 per TA.
    assert want.min() >= 1 and want.max() <= 2 * n
    assert np.abs(want.astype(int) - np.asarray(ta, dtype=int)).max() <= 1
