"""Data subsystems: iris booleanization, block CV, filter, ring buffer,
and the MNIST-scale procedural digit generator."""
import hashlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import blocks, buffer, filter as filt, iris, memory, mnist


def test_iris_shape_and_balance():
    xs, ys = iris.load()
    assert xs.shape == (150, 16) and xs.dtype == bool
    assert list(np.bincount(ys)) == [50, 50, 50]


def test_thermometer_monotone():
    """Thermometer code: higher bit set => all lower bits set."""
    xs, _ = iris.load()
    b = xs.reshape(150, 4, 4)
    for k in range(3):
        assert np.all(b[:, :, k] >= b[:, :, k + 1])


def test_orderings_are_permutations():
    o = blocks.all_orderings(5)
    assert o.shape == (120, 5)
    assert np.all(np.sort(o, axis=1) == np.arange(5))
    sub = blocks.select_orderings(5, 10, seed=1)
    assert sub.shape == (10, 5)
    assert len({tuple(r) for r in sub}) == 10


def test_sets_partition_dataset():
    """Every ordering's 3 sets must partition the 150 rows exactly."""
    sets, spec = blocks.iris_paper_sets(n_orderings=6)
    xs, ys = iris.load()
    assert spec.sizes() == (30, 60, 60)
    for o in range(6):
        rows = np.concatenate(
            [sets.offline_x[o], sets.validation_x[o], sets.online_x[o]]
        )
        # sort rows of both and compare as multisets
        a = np.sort(rows.view(np.uint8).reshape(150, -1), axis=0)
        b = np.sort(xs.view(np.uint8).reshape(150, -1), axis=0)
        np.testing.assert_array_equal(a, b)


def test_class_filter_mask():
    ys = jnp.asarray([0, 1, 2, 1, 0])
    m = filt.class_filter_mask(ys, jnp.int32(1), jnp.bool_(True))
    np.testing.assert_array_equal(np.asarray(m), [True, False, True, False, True])
    m_off = filt.class_filter_mask(ys, jnp.int32(1), jnp.bool_(False))
    assert bool(jnp.all(m_off))


def test_limit_mask():
    m = filt.limit_mask(30, jnp.int32(20))
    assert int(jnp.sum(m)) == 20 and bool(m[19]) and not bool(m[20])


def test_ring_buffer_fifo():
    buf = buffer.make(4, 3)
    xs = [jnp.asarray([i % 2, 1, 0], dtype=bool) for i in range(5)]
    for i in range(4):
        buf, ok = buffer.push(buf, xs[i], jnp.int32(i))
        assert bool(ok)
    buf, ok = buffer.push(buf, xs[4], jnp.int32(4))
    assert not bool(ok)  # full -> reject (backpressure)
    got = []
    for _ in range(5):
        buf, x, y, valid = buffer.pop(buf)
        if bool(valid):
            got.append(int(y))
    assert got == [0, 1, 2, 3]  # FIFO order, nothing dropped silently


def test_ring_buffer_wraparound():
    buf = buffer.make(2, 1)
    on = jnp.asarray([1], dtype=bool)
    for round_ in range(3):
        buf, ok = buffer.push(buf, on, jnp.int32(10 + round_))
        assert bool(ok)
        buf, x, y, valid = buffer.pop(buf)
        assert bool(valid) and int(y) == 10 + round_
    assert int(buf.size) == 0


def _push_rows(buf, xs, ys, count):
    """The oracle: ``count`` single-row pushes, in order."""
    accepted = 0
    for i in range(min(count, xs.shape[0])):
        buf, ok = buffer.push(buf, xs[i], ys[i])
        accepted += int(ok)
    return buf, accepted


def _filled_ring(rng, cap, width, head, size, packed):
    """A ring with random contents (stale slots included) at head/size."""
    buf = buffer.make(cap, 32 * width if packed else width, packed=packed)
    if packed:
        data_x = rng.integers(0, 2**32, (cap, width), dtype=np.uint32)
    else:
        data_x = rng.random((cap, width)) < 0.5
    return buf._replace(
        data_x=jnp.asarray(data_x),
        data_y=jnp.asarray(rng.integers(0, 10, cap, dtype=np.int32)),
        head=jnp.int32(head), size=jnp.int32(size),
    )


def _staged_rows(rng, block, width, packed):
    if packed:
        xs = rng.integers(0, 2**32, (block, width), dtype=np.uint32)
    else:
        xs = rng.random((block, width)) < 0.5
    return jnp.asarray(xs), jnp.asarray(
        rng.integers(0, 10, block, dtype=np.int32))


def _assert_rings_equal(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


# (cap, block, head, size, count): rings that wrap, empty, nearly full and
# full rings (rows rejected), counts 0, 1, B-1 and B, B == cap, B > cap,
# and capacities that are not powers of two.
PUSH_BLOCK_CASES = [
    (8, 4, 6, 1, 4),     # tail 7: the block wraps past the ring's end
    (8, 4, 7, 0, 3),     # empty ring, head at the last slot
    (8, 4, 0, 0, 4),     # empty ring
    (8, 4, 2, 6, 4),     # nearly full: 2 land, 2 rejected
    (8, 4, 5, 7, 1),     # one slot left, one row
    (8, 4, 5, 8, 3),     # full: every row rejected
    (8, 4, 3, 2, 0),     # nothing staged
    (8, 4, 1, 3, 3),     # count B - 1
    (6, 6, 4, 0, 6),     # B == cap, empty ring, wraps
    (6, 6, 4, 3, 6),     # B == cap, half full
    (7, 3, 5, 2, 3),     # cap not a power of two, wraps
    (5, 5, 3, 4, 5),     # cap 5: one lands
    (1, 1, 0, 0, 1),     # one-slot ring
    (4, 6, 3, 2, 6),     # a block longer than the ring: 2 land
    (64, 32, 50, 20, 32),  # the benchmark's ring and block
]


@pytest.mark.parametrize("packed", [True, False], ids=["uint32", "bool"])
@pytest.mark.parametrize("cap,block,head,size,count", PUSH_BLOCK_CASES)
def test_push_block_equals_push_loop(cap, block, head, size, count, packed):
    """A block lands bitwise as ``count`` single-row pushes would leave the
    ring: contents, order, head, size and the accepted count."""
    rng = np.random.default_rng(cap * 1000 + head * 10 + size)
    width = 2 if packed else 5
    buf = _filled_ring(rng, cap, width, head, size, packed)
    xs, ys = _staged_rows(rng, block, width, packed)
    want, want_acc = _push_rows(buf, xs, ys, count)
    got, got_acc = jax.jit(buffer.push_block)(buf, xs, ys, jnp.int32(count))
    _assert_rings_equal(got, want)
    assert np.asarray(got_acc).dtype == np.int32
    assert int(got_acc) == want_acc == min(count, cap - size)


def test_mnist_shapes_and_class_balance():
    """Every class appears exactly n/10 times when 10 | n, at every side."""
    for side in (28, 14, 7):
        xs, ys = mnist.load(n_points=60, side=side)
        assert xs.shape == (60, side * side) and xs.dtype == bool
        assert ys.dtype == np.int32
        assert list(np.bincount(ys, minlength=10)) == [6] * 10
    # uneven n: counts differ by at most one
    ys = mnist.labels(47, seed=3)
    counts = np.bincount(ys, minlength=10)
    assert counts.max() - counts.min() <= 1 and counts.sum() == 47


def test_mnist_deterministic_across_processes():
    """Same seed => bitwise-same splits, even in a fresh interpreter (the
    generator draws from SeedSequence([seed, i]), never global RNG state)."""
    tr_x, tr_y, te_x, te_y = mnist.splits(20, 10, seed=7, side=7)
    digest = hashlib.sha256(
        b"".join(np.ascontiguousarray(a).tobytes()
                 for a in (tr_x, tr_y, te_x, te_y))
    ).hexdigest()
    child = subprocess.run(
        [sys.executable, "-c", (
            "import hashlib, numpy as np\n"
            "from repro.data import mnist\n"
            "parts = mnist.splits(20, 10, seed=7, side=7)\n"
            "print(hashlib.sha256(b''.join("
            "np.ascontiguousarray(a).tobytes() for a in parts)).hexdigest())"
        )],
        capture_output=True, text=True, check=True,
    )
    assert child.stdout.strip() == digest


def test_mnist_splits_are_prefix_stable():
    """Growing the test split never perturbs the train rows (one
    generation, sliced)."""
    a = mnist.splits(20, 5, seed=1, side=7)
    b = mnist.splits(20, 15, seed=1, side=7)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2][:5])


def test_mnist_booleanize_threshold_edge():
    """Booleanization is inclusive: a pixel exactly at the threshold is
    ink; one ulp below is background."""
    thr = mnist.THRESHOLD
    below = np.nextafter(np.float32(thr), np.float32(0.0))
    imgs = np.asarray([[[thr, below], [0.0, 1.0]]], dtype=np.float32)
    bits = mnist.booleanize(imgs)
    np.testing.assert_array_equal(bits, [[True, False, False, True]])


def test_mnist_downscale_blocks():
    """Block-mean pooling halves the raster and averages exact 2x2 blocks."""
    imgs = np.arange(16, dtype=np.float32).reshape(1, 4, 4)
    got = mnist.downscale(imgs, 2)
    np.testing.assert_allclose(
        got, [[[2.5, 4.5], [10.5, 12.5]]]
    )
    with pytest.raises(ValueError):
        mnist.downscale(np.zeros((1, 7, 7), dtype=np.float32), 2)


def test_mnist_glyphs_separable_at_low_res():
    """Different digits produce different booleanized rasters even at 7x7
    (jitter never collapses two classes onto one bitmap)."""
    xs, ys = mnist.load(n_points=40, side=7)
    for a in range(40):
        for b in range(a + 1, 40):
            if ys[a] != ys[b]:
                assert not np.array_equal(xs[a], xs[b])


def test_mnist_downscale_preserves_label_assignment():
    """Hypothesis property: the 28 -> 14 -> 7 downscale chain is a pure
    datapath-width change — the label sequence depends only on (n, seed),
    and block-pooling a 28x28 raster twice matches the 7x7 geometry."""
    pytest.importorskip(
        "hypothesis", reason="optional dev dependency (requirements-dev.txt)"
    )
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(10, 30), seed=st.integers(0, 2**16 - 1))
    def prop(n, seed):
        ys28 = mnist.load(n_points=n, seed=seed, side=28)[1]
        ys14 = mnist.load(n_points=n, seed=seed, side=14)[1]
        ys7 = mnist.load(n_points=n, seed=seed, side=7)[1]
        np.testing.assert_array_equal(ys28, ys14)
        np.testing.assert_array_equal(ys14, ys7)
        imgs28, ys = mnist.raw(n, seed=seed, side=28)
        pooled7 = mnist.downscale(mnist.downscale(imgs28, 2), 2)
        assert pooled7.shape == (n, 7, 7)
        np.testing.assert_array_equal(ys, ys28)
        # pooled ink stays ink-like: every digit keeps some over-threshold
        # mass after two halvings
        assert (pooled7.reshape(n, -1) >= mnist.THRESHOLD).any(axis=1).all()

    prop()


def test_rom_source_cycles():
    xs = np.eye(3, dtype=bool)
    ys = np.arange(3, dtype=np.int32)
    src = memory.ROMSource(xs, ys)
    seen = [src.next_row()[1] for _ in range(7)]
    assert seen == [0, 1, 2, 0, 1, 2, 0]
