"""Bit-packed datapath suite (DESIGN.md §13).

Pins the packed-representation contract end to end:

* pack/unpack round-trip at arbitrary widths (hypothesis: any n, including
  non-multiples of 32) with the tail-word bits provably zero,
* the jax and numpy packers produce the SAME words (the router packs
  host-side, the kernels consume device-side — one layout),
* packed clause eval is bitwise identical to the unpacked oracle on BOTH
  backends, batch + replicated, across word-boundary-crossing widths,
* the fault controller commutes with packing (stuck-at applied pre-pack ==
  applied in the packed domain).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TMConfig, faults, init_runtime, init_state
from repro.core import tm as tm_mod
from repro.kernels import dispatch, packing, ref

# f values straddling word boundaries: sub-word, word-1, word, word+1,
# multi-word with tail, and the benchmark widths.
WIDTHS = [5, 16, 31, 32, 33, 49, 196, 513, 784]


# ---------------------------------------------------------------------------
# layout: round-trip, tail-bit contract, jax/numpy agreement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", WIDTHS)
def test_pack_unpack_round_trip(n):
    rng = np.random.default_rng(n)
    bits = rng.random((7, n)) < 0.5
    words = packing.pack_bits(jnp.asarray(bits))
    assert words.shape == (7, packing.n_words(n))
    assert words.dtype == jnp.uint32
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_bits(words, n)), bits
    )


def _roundtrip_and_tail_property(n, seed):
    """The §13 layout property at one (width, seed): round-trip exact,
    tail bits provably zero, numpy/jax packers agree word for word."""
    rng = np.random.default_rng(seed)
    bits = rng.random((3, n)) < 0.5
    words = np.asarray(packing.pack_bits(jnp.asarray(bits)))
    np.testing.assert_array_equal(packing.pack_bits_np(bits), words)
    np.testing.assert_array_equal(
        np.asarray(packing.unpack_bits(jnp.asarray(words), n)), bits
    )
    np.testing.assert_array_equal(packing.unpack_bits_np(words, n), bits)
    # The tail contract the kernels rely on: no bit above position n-1.
    tail = words[..., -1]
    assert (tail & ~np.uint32(packing.tail_mask(n))).max(initial=0) == 0
    # LSB-first word-major: bit i of word w is element 32w + i.
    w, i = (n - 1) // 32, (n - 1) % 32
    np.testing.assert_array_equal(
        (words[:, w] >> np.uint32(i)) & 1, bits[:, n - 1].astype(np.uint32)
    )


@pytest.mark.parametrize("n", WIDTHS + [1, 63, 64, 65])
def test_pack_round_trip_and_tail_zero_sweep(n):
    """Deterministic width sweep of the layout property (always runs)."""
    _roundtrip_and_tail_property(n, seed=n * 7919)


def test_pack_property_arbitrary_widths():
    """Hypothesis form: ANY width in [1, 300], incl. non-multiples of 32."""
    pytest.importorskip(
        "hypothesis", reason="optional dev dependency (requirements-dev.txt)"
    )
    from hypothesis import given, settings, strategies as st

    @given(n=st.integers(min_value=1, max_value=300),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=60, deadline=None)
    def prop(n, seed):
        _roundtrip_and_tail_property(n, seed)

    prop()


@pytest.mark.parametrize("f", [5, 31, 33, 49])
def test_literal_layout_two_halves(f):
    """pack_literals == [pack(x), pack(~x)], and literals_from_packed
    derives the same words from packed features by pure word ops."""
    rng = np.random.default_rng(f)
    x = jnp.asarray(rng.random((4, f)) < 0.5)
    lit = packing.pack_literals(x)
    assert lit.shape == (4, packing.lit_words(f))
    np.testing.assert_array_equal(
        np.asarray(lit),
        np.concatenate(
            [packing.pack_bits_np(np.asarray(x)),
             packing.pack_bits_np(~np.asarray(x))], axis=-1,
        ),
    )
    np.testing.assert_array_equal(
        np.asarray(packing.literals_from_packed(packing.pack_bits(x), f)),
        np.asarray(lit),
    )


@pytest.mark.parametrize("f", [5, 31, 33, 49])
def test_pack_include_matches_literal_positions(f):
    """pack_include's split puts include bit l at the same (word, bit) as
    literal l in pack_literals — checked via unpack round-trip per half."""
    rng = np.random.default_rng(100 + f)
    inc = rng.random((3, 2, 2 * f)) < 0.3
    words = np.asarray(packing.pack_include(jnp.asarray(inc), f))
    Wf = packing.n_words(f)
    np.testing.assert_array_equal(
        packing.unpack_bits_np(words[..., :Wf], f), inc[..., :f]
    )
    np.testing.assert_array_equal(
        packing.unpack_bits_np(words[..., Wf:], f), inc[..., f:]
    )


# ---------------------------------------------------------------------------
# packed vs unpacked clause eval: bitwise parity on both backends
# ---------------------------------------------------------------------------


def _packed_one(kb, inc_p, lit_p, training):
    """The packed batch entry at one machine: [C, J, W] x [B, W] ->
    [B, C, J]."""
    return kb.clause_eval_batch_replicated_packed(
        inc_p[None], lit_p[None], training=training)[0]


def _case(f, seed, C=3, J=6, B=17):
    rng = np.random.default_rng(seed)
    include = jnp.asarray(rng.random((C, J, 2 * f)) < 0.3)
    x = jnp.asarray(rng.random((B, f)) < 0.5)
    lits = jnp.concatenate([x, ~x], axis=-1)
    return include, lits, packing.pack_include(include, f), \
        packing.pack_literals(x)


@pytest.mark.parametrize("f", WIDTHS)
@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_clause_eval_batch_packed_matches_unpacked(f, mod):
    mod = dispatch.resolve(mod)
    include, lits, inc_p, lit_p = _case(f, seed=f)
    for training in (True, False):
        want = ref.clause_eval_loop(include, lits, training=training)
        got = _packed_one(mod, inc_p, lit_p, training)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_clause_eval_batch_packed_empty_and_all_include(mod):
    """Edge banks: all-excluded (empty convention) and all-included."""
    mod = dispatch.resolve(mod)
    f = 33
    inc_empty = jnp.zeros((2, 4, 2 * f), dtype=bool)
    inc_full = jnp.ones((2, 4, 2 * f), dtype=bool)
    x = jnp.asarray(np.random.default_rng(0).random((5, f)) < 0.5)
    lit_p = packing.pack_literals(x)
    for inc in (inc_empty, inc_full):
        inc_p = packing.pack_include(inc, f)
        for training in (True, False):
            want = ref.clause_eval_loop(
                inc, jnp.concatenate([x, ~x], axis=-1), training=training
            )
            got = _packed_one(mod, inc_p, lit_p, training)
            np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("f", [16, 31, 49, 196])
@pytest.mark.parametrize("RD", [(1, 1), (4, 2), (3, 3)])
@pytest.mark.parametrize("mod", ["ref", "pallas"])
def test_clause_eval_batch_replicated_packed_matches_unpacked(f, RD, mod):
    mod = dispatch.resolve(mod)
    R, D = RD
    rng = np.random.default_rng(hash((f, R, D)) % 2**31)
    include = jnp.asarray(rng.random((R, 3, 6, 2 * f)) < 0.3)
    x = jnp.asarray(rng.random((D, 9, f)) < 0.5)
    lits = jnp.concatenate([x, ~x], axis=-1)
    inc_p = packing.pack_include(include, f)
    lit_p = packing.pack_literals(x)
    for training in (True, False):
        want = ref.clause_eval_batch_replicated(
            include, lits, training=training
        )
        got = mod.clause_eval_batch_replicated_packed(
            inc_p, lit_p, training=training
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_packed_replicated_rejects_bad_data_axis():
    f = 16
    inc_p = packing.pack_include(jnp.zeros((4, 1, 2, 2 * f), bool), f)
    lit_p = packing.pack_literals(jnp.zeros((3, 5, f), bool))
    with pytest.raises(ValueError, match="must divide"):
        ref.clause_eval_batch_replicated_packed(inc_p, lit_p, training=False)


@pytest.mark.parametrize("f", [16, 49])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_forward_batch_routes_packed_by_dtype(f, backend):
    """forward_batch/predict on packed uint32 rows == on bool rows."""
    cfg = TMConfig(n_features=f, max_classes=3, max_clauses=8, n_states=50,
                   backend=backend)
    st = init_state(cfg)
    rt = init_runtime(cfg)
    rng = np.random.default_rng(f)
    xs = jnp.asarray(rng.random((11, f)) < 0.5)
    xp = packing.pack_bits(xs)
    for training in (True, False):
        cl_a, v_a = tm_mod.forward_batch(cfg, st, rt, xs, training=training)
        cl_b, v_b = tm_mod.forward_batch(cfg, st, rt, xp, training=training)
        np.testing.assert_array_equal(np.asarray(cl_a), np.asarray(cl_b))
        np.testing.assert_array_equal(np.asarray(v_a), np.asarray(v_b))


# ---------------------------------------------------------------------------
# fault controller commutes with packing (§3.1.2 in the packed domain)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("f", [16, 49])
@pytest.mark.parametrize("stuck_value", [0, 1])
def test_stuck_at_faults_commute_with_packing(f, stuck_value):
    """Fault applied pre-pack == fault applied on packed include words."""
    cfg = TMConfig(n_features=f, max_classes=3, max_clauses=8, n_states=50)
    st = init_state(cfg, key=None)
    rng = np.random.default_rng(f + stuck_value)
    st = st._replace(ta_state=jnp.asarray(
        rng.integers(1, 2 * cfg.n_states + 1,
                     st.ta_state.shape).astype(np.int8)
    ))
    a, o = faults.random_stuck_at(cfg, 0.1, stuck_value, seed=7)
    rt = faults.inject(init_runtime(cfg), a, o)

    # pre-pack: the faulted include plane, then packed (what the packed
    # datapath actually runs via ta_actions_packed)
    pre = tm_mod.ta_actions_packed(cfg, st, rt)

    # packed domain: pack the clean include plane and the fault mappings,
    # then run the AND/OR circuit on words
    clean = tm_mod.ta_actions(cfg, st, faults.clear(cfg, init_runtime(cfg)))
    a_p, o_p = faults.packed_masks(cfg, rt)
    post = faults.apply_packed(packing.pack_include(clean, f), a_p, o_p)

    np.testing.assert_array_equal(np.asarray(pre), np.asarray(post))
    # and both keep the tail-bit contract
    Wf = packing.n_words(f)
    tail = np.uint32(packing.tail_mask(f))
    for words in (np.asarray(pre), np.asarray(post)):
        assert (words[..., Wf - 1] & ~tail).max(initial=0) == 0
        assert (words[..., -1] & ~tail).max(initial=0) == 0


def test_faulted_packed_eval_matches_unpacked(backend="pallas"):
    """Stuck-at faults flow through the packed clause kernels bitwise."""
    f = 49
    cfg = TMConfig(n_features=f, max_classes=2, max_clauses=6, n_states=50,
                   backend=backend)
    rng = np.random.default_rng(3)
    st = init_state(cfg)._replace(ta_state=jnp.asarray(
        rng.integers(1, 2 * cfg.n_states + 1,
                     (2, 6, 2 * f)).astype(np.int8)
    ))
    a, o = faults.even_spread_stuck_at(cfg, 0.2, 1)
    rt = faults.inject(init_runtime(cfg), a, o)
    xs = jnp.asarray(rng.random((13, f)) < 0.5)
    cl_a, v_a = tm_mod.forward_batch(cfg, st, rt, xs)
    cl_b, v_b = tm_mod.forward_batch(cfg, st, rt, packing.pack_bits(xs))
    np.testing.assert_array_equal(np.asarray(cl_a), np.asarray(cl_b))
    np.testing.assert_array_equal(np.asarray(v_a), np.asarray(v_b))
