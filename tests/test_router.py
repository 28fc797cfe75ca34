"""BatchRouter ingress properties: nothing lost, nothing reordered.

The router defers device enqueues (host-side staging, packed block
flushes), so the property that matters is conservation + FIFO order per
replica under ARBITRARY interleavings of submit / submit_rows / flush /
drain / tick: every accepted datapoint reaches its replica's ring buffer
exactly once, in submission order, and every rejected one is a counted
backpressure drop. Rows are tagged with a unique id encoded in the
feature bits so reordering cannot hide.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TMConfig, init_state
from repro.data import buffer
from repro.serve import AdaptPolicy, ServiceConfig, TMService
from repro.serve import router as router_mod

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dev dependency (requirements-dev.txt)
    HAVE_HYPOTHESIS = False

K, CAP, BLOCK, CHUNK, F = 3, 6, 3, 4, 16


def _make_service(seed=0):
    cfg = TMConfig(n_features=F, max_classes=3, max_clauses=16, n_states=16)
    return TMService(cfg, init_state(cfg), ServiceConfig(
        replicas=K, buffer_capacity=CAP, chunk=CHUNK, ingress_block=BLOCK,
        s=3.0, T=15, seed=seed,
    ))


def _row(uid: int):
    """A unique datapoint: uid's bits as features (16 bits = plenty)."""
    x = np.array([(uid >> b) & 1 for b in range(F)], dtype=bool)
    return x, uid % 3


def _uid(x: np.ndarray) -> int:
    return int(sum(int(v) << b for b, v in enumerate(x)))


def _device_queue(svc, r):
    """Replica r's ring-buffer content, oldest first, as uids."""
    buf = svc.ss.buf
    data_x = np.asarray(buf.data_x[r])
    head = int(np.asarray(buf.head[r]))
    size = int(np.asarray(buf.size[r]))
    return [_uid(data_x[(head + i) % CAP]) for i in range(size)]


class _Model:
    """Host-side reference: per-replica FIFO + conservation counters."""

    def __init__(self):
        self.queue = [[] for _ in range(K)]   # accepted, not yet trained
        self.submitted = np.zeros(K, dtype=np.int64)
        self.dropped = np.zeros(K, dtype=np.int64)
        self.trained = np.zeros(K, dtype=np.int64)

    def submit(self, r, uid) -> bool:
        self.submitted[r] += 1
        if len(self.queue[r]) >= CAP:
            self.dropped[r] += 1
            return False
        self.queue[r].append(uid)
        return True

    def drain(self, budget):
        out = []
        for r in range(K):
            n = min(int(budget[r]), len(self.queue[r]))
            del self.queue[r][:n]
            self.trained[r] += n
            out.append(n)
        return np.asarray(out)


def _check(svc, model):
    """Conservation + order invariants (order checked on device after a
    forced flush so staged rows are visible in the ring)."""
    np.testing.assert_array_equal(svc.buffered,
                                  [len(q) for q in model.queue])
    np.testing.assert_array_equal(svc.dropped, model.dropped)
    # conservation: every submitted point is trained, queued or dropped
    np.testing.assert_array_equal(
        model.submitted,
        model.trained + svc.buffered + model.dropped,
    )
    svc.flush()
    for r in range(K):
        assert _device_queue(svc, r) == model.queue[r], (
            f"replica {r}: device ring diverged from FIFO model"
        )


if HAVE_HYPOTHESIS:
    _ops = st.lists(
        st.one_of(
            st.tuples(st.just("submit"), st.integers(0, K - 1)),
            st.tuples(st.just("submit_rows"),
                      st.integers(1, 2 ** K - 1)),     # nonempty mask bits
            st.tuples(st.just("flush"), st.just(0)),
            st.tuples(st.just("drain"), st.integers(0, 2 * CAP)),
            st.tuples(st.just("tick"), st.integers(0, CHUNK)),
        ),
        max_size=30,
    )

    @settings(max_examples=20, deadline=None)
    @given(ops_seq=_ops, seed=st.integers(0, 2 ** 31 - 1))
    def test_router_no_loss_no_reorder(ops_seq, seed):
        """Arbitrary submit/submit_rows/flush/drain/tick interleavings:
        per-replica FIFO order and datapoint conservation always hold."""
        svc = _make_service(seed)
        model = _Model()
        uid = 0
        for op, arg in ops_seq:
            if op == "submit":
                uid += 1
                x, y = _row(uid)
                assert svc.submit(arg, x, y) == model.submit(arg, uid)
            elif op == "submit_rows":
                uid += 1
                x, y = _row(uid)
                mask = np.array([(arg >> r) & 1 for r in range(K)],
                                dtype=bool)
                got = svc.submit_rows(x, y, mask)
                want = np.array([model.submit(r, uid) if mask[r] else False
                                 for r in range(K)])
                np.testing.assert_array_equal(got, want)
            elif op == "flush":
                svc.flush()
            elif op == "drain":
                np.testing.assert_array_equal(svc.drain(arg),
                                              model.drain([arg] * K))
            else:  # tick (no eval set: drains + cadence only)
                rep = svc.tick(arg)
                np.testing.assert_array_equal(rep.trained,
                                              model.drain([arg] * K))
                assert rep.accuracy is None
        _check(svc, model)


def test_router_block_flush_counts():
    """Auto-flush fires when a staging lane fills: N submits per replica
    cost ceil(N / B_ingress) dispatches, and explicit flush is a no-op
    when nothing is staged."""
    svc = _make_service()
    uid = 0
    for _ in range(BLOCK):        # fill every lane exactly once
        uid += 1
        x, y = _row(uid)
        svc.submit_rows(x, y)
    assert svc.router.flushes == 1      # lanes hit BLOCK -> one dispatch
    np.testing.assert_array_equal(svc.router.staged, [0] * K)
    svc.flush()
    assert svc.router.flushes == 1      # nothing staged: no dispatch
    uid += 1
    x, y = _row(uid)
    svc.submit(0, x, y)
    svc.flush()
    assert svc.router.flushes == 2
    np.testing.assert_array_equal(svc.buffered, [BLOCK + 1, BLOCK, BLOCK])


def test_router_rejects_against_mirror_not_device():
    """Acceptance is decided host-side: a full buffer (device + staged)
    rejects synchronously even though no device dispatch happened yet."""
    svc = _make_service()
    for i in range(CAP):
        x, y = _row(i + 1)
        assert svc.submit(0, x, y)
    x, y = _row(99)
    assert not svc.submit(0, x, y)            # full purely from staging
    np.testing.assert_array_equal(svc.dropped, [1, 0, 0])
    svc.drain(2)                               # frees two slots
    assert svc.submit(0, x, y)
    np.testing.assert_array_equal(svc.buffered, [CAP - 1, 0, 0])


def test_submit_rows_broadcast_contract():
    """The old offer_rows broadcast rules survive the router: [f] and
    [1, f] features (and scalar / [1] labels) fan out to all K replicas."""
    svc = _make_service()
    x, y = _row(5)
    for xs, ys in [(x, y), (x[None], np.asarray([y])),
                   (np.broadcast_to(x, (K, F)), np.full(K, y))]:
        np.testing.assert_array_equal(svc.submit_rows(xs, ys), [True] * K)
    svc.flush()
    for r in range(K):
        assert _device_queue(svc, r) == [5, 5, 5]


def test_mirror_survives_on_chunk_exception():
    """A callback raising mid-drain leaves device state, occupancy mirror
    and acceptance accounting consistent (no phantom backpressure)."""
    svc = _make_service()
    for i in range(CAP):
        x, y = _row(i + 1)
        assert svc.submit(0, x, y)

    class Boom(Exception):
        pass

    calls = []

    def boom(aux):
        calls.append(aux)
        raise Boom

    with pytest.raises(Boom):
        svc.drain(CAP, on_chunk=boom)   # CHUNK < CAP: raises on chunk 1
    assert len(calls) == 1
    consumed = CHUNK                     # exactly one chunk landed
    np.testing.assert_array_equal(svc.buffered, [CAP - consumed, 0, 0])
    np.testing.assert_array_equal(
        svc.buffered[0], int(np.asarray(svc.ss.buf.size[0]))
    )
    x, y = _row(99)
    assert svc.submit(0, x, y)           # no phantom backpressure
    assert svc.drain(2 * CAP)[0] == CAP - consumed + 1


def test_take_block_returns_stable_double_buffered_arrays():
    """Regression (the tentpole's prerequisite bug): take_block used to
    return the LIVE staging arrays and reset counts in place, so any stage
    call racing a flush-in-progress wrote into the block being
    transferred. With double buffering the taken block must stay frozen
    while producers keep staging."""
    from repro.serve.router import BatchRouter

    r = BatchRouter(K, F, capacity=CAP, block=BLOCK)
    dev = np.zeros(K, dtype=np.int64)
    full = np.ones(K, dtype=bool)
    for uid in (1, 2):
        x, y = _row(uid)
        acc, blocked = r.stage_rows(np.broadcast_to(x, (K, F)),
                                    np.full(K, y), full, dev)
        assert acc.all() and not blocked.any()
    xs, ys, counts = r.take_block()
    snap_x, snap_y = xs.copy(), ys.copy()
    np.testing.assert_array_equal(counts, [2] * K)
    # producers keep staging DURING the (simulated) transfer
    for uid in (7, 8, 9):
        x, y = _row(uid)
        r.stage_rows(np.broadcast_to(x, (K, F)), np.full(K, y), full, dev)
    np.testing.assert_array_equal(xs, snap_x)   # taken block untouched
    np.testing.assert_array_equal(ys, snap_y)
    # the swap alternates blocks: the next take hands over the new rows
    xs2, _, counts2 = r.take_block()
    np.testing.assert_array_equal(counts2, [3] * K)
    assert _uid(xs2[0, 0]) == 7 and _uid(xs2[0, 2]) == 9


def test_take_lanes_scopes_to_named_replicas():
    """take_lanes pulls ONLY the named lanes (the scoped-flush path for
    TMService.evict): other lanes stay staged, no block swap happens,
    and the taken rows come out in submission order."""
    from repro.serve.router import BatchRouter

    r = BatchRouter(K, F, capacity=CAP, block=BLOCK)
    dev = np.zeros(K, dtype=np.int64)
    full = np.ones(K, dtype=bool)
    for uid in (1, 2):
        x, y = _row(uid)
        acc, _ = r.stage_rows(np.broadcast_to(x, (K, F)),
                              np.full(K, y), full, dev)
        assert acc.all()
    taken = r.take_lanes([2, 0])
    assert taken is not None
    xs, ys, counts = taken
    np.testing.assert_array_equal(counts, [2, 2])
    for lane in range(2):
        assert [_uid(xs[lane, c]) for c in range(2)] == [1, 2]
    np.testing.assert_array_equal(r.staged, [0, 2, 0])   # lane 1 untouched
    assert r.flushes == 0                                # no block swap
    assert r.take_lanes([0, 2]) is None                  # now empty
    xs2, _, counts2 = r.take_block()                     # lane 1 still there
    np.testing.assert_array_equal(counts2, [0, 2, 0])
    assert _uid(xs2[1, 0]) == 1


if HAVE_HYPOTHESIS:
    _stage_take_ops = st.lists(
        st.one_of(
            st.tuples(st.just("stage"), st.integers(1, 2 ** K - 1)),
            st.tuples(st.just("take"), st.just(0)),
        ),
        max_size=40,
    )

    @settings(max_examples=30, deadline=None)
    @given(ops_seq=_stage_take_ops)
    def test_router_stage_take_interleaving(ops_seq):
        """Arbitrary stage/take interleavings through the double-buffered
        blocks: per replica, the concatenation of taken blocks is exactly
        the accepted rows in submission order — nothing lost, duplicated,
        or reordered."""
        from repro.serve.router import BatchRouter

        r = BatchRouter(K, F, capacity=10 ** 6, block=BLOCK)
        dev = np.zeros(K, dtype=np.int64)
        staged = [[] for _ in range(K)]   # accepted, not yet taken
        taken = [[] for _ in range(K)]
        uid = 0
        for op, arg in ops_seq:
            if op == "stage":
                uid += 1
                x, y = _row(uid)
                mask = np.array([(arg >> i) & 1 for i in range(K)],
                                dtype=bool)
                acc, blocked = r.stage_rows(
                    np.broadcast_to(x, (K, F)), np.full(K, y), mask, dev
                )
                # lane-full replicas block (capacity is huge: never drop)
                np.testing.assert_array_equal(acc | blocked, mask)
                for i in np.nonzero(acc)[0]:
                    staged[i].append(uid)
            else:
                blk = r.take_block()
                if blk is None:
                    assert not any(staged), "rows staged but take gave None"
                    continue
                xs, ys, counts = blk
                for i in range(K):
                    got = [_uid(xs[i, c]) for c in range(int(counts[i]))]
                    taken[i].extend(got)
                    assert staged[i][:len(got)] == got, (
                        f"replica {i}: taken block out of order"
                    )
                    del staged[i][:len(got)]
        while (blk := r.take_block()) is not None:
            xs, ys, counts = blk
            for i in range(K):
                taken[i].extend(_uid(xs[i, c])
                                for c in range(int(counts[i])))
                del staged[i][:int(counts[i])]
        assert not any(staged)   # conservation: everything staged came out


def _make_packed_service(seed=0):
    cfg = TMConfig(n_features=F, max_classes=3, max_clauses=16, n_states=16)
    return TMService(cfg, init_state(cfg), ServiceConfig(
        replicas=K, buffer_capacity=CAP, chunk=CHUNK, ingress_block=BLOCK,
        s=3.0, T=15, seed=seed, packed=True,
    ))


def test_packed_submit_routes_prepacked_uint32_rows():
    """On a packed service, already-packed uint32 word rows pass through
    the staging boundary verbatim — previously `asarray(xs, dtype=bool)`
    silently mangled them into all-ones rows."""
    from repro.kernels.packing import pack_bits_np

    svc_bool, svc_words = _make_packed_service(), _make_packed_service()
    for uid in (5, 9, 1034):
        x, y = _row(uid)
        a = svc_bool.submit_rows(x, y)
        b = svc_words.submit_rows(pack_bits_np(x[None])[0], y)
        np.testing.assert_array_equal(a, b)
    svc_bool.flush(), svc_words.flush()
    for name in ("data_x", "data_y", "head", "size"):
        np.testing.assert_array_equal(
            np.asarray(getattr(svc_bool.ss.buf, name)),
            np.asarray(getattr(svc_words.ss.buf, name)),
        )
    assert np.asarray(svc_words.ss.buf.data_x).dtype == np.uint32


def test_unpacked_submit_rejects_uint32_rows():
    """uint32 rows into an UNPACKED service are a hard error, not a
    silent astype(bool) mangle."""
    svc = _make_service()
    x, y = _row(3)
    packed_row = np.zeros(1, dtype=np.uint32)
    packed_row[0] = 3
    with pytest.raises(TypeError, match="packed"):
        svc.submit_rows(packed_row, y)
    np.testing.assert_array_equal(svc.buffered, [0] * K)   # nothing staged
    assert svc.submit_rows(x, y).all()                     # bool path fine


def test_service_history_limit_bounds_growth():
    """A long-running service's analysis history is a memory leak at
    traffic scale; history_limit keeps only the most recent entries."""
    cfg = TMConfig(n_features=F, max_classes=3, max_clauses=16, n_states=16)
    from repro.data import iris  # noqa: F401  (not needed; uid rows do)

    xs = np.stack([_row(i + 1)[0] for i in range(8)])
    ys = np.asarray([_row(i + 1)[1] for i in range(8)], dtype=np.int32)

    def build(limit):
        return TMService(cfg, init_state(cfg), ServiceConfig(
            replicas=K, buffer_capacity=CAP, chunk=CHUNK, s=3.0, T=15,
            history_limit=limit,
        ), eval_x=xs, eval_y=ys)

    unbounded, bounded = build(None), build(3)
    for _ in range(7):
        unbounded.analyze(), bounded.analyze()
    assert len(unbounded.history) == 7          # legacy behavior
    assert len(bounded.history) == 3            # bounded at the knob
    # the kept entries are the most recent ones, still in order
    for (s_u, a_u), (s_b, a_b) in zip(unbounded.history[-3:],
                                      bounded.history):
        np.testing.assert_array_equal(s_u, s_b)
        np.testing.assert_array_equal(a_u, a_b)
    with pytest.raises(ValueError, match="history_limit"):
        build(0)


def test_service_config_validates_port_lengths():
    """Per-replica s/T sequences must match `replicas` at construction,
    like the seed check — not fail deep in the first drained kernel."""
    from repro.core import TMConfig, init_state
    from repro.serve import ServiceConfig, TMService

    cfg = TMConfig(n_features=F, max_classes=3, max_clauses=16, n_states=16)
    for bad in (dict(s=[1.0, 2.0]), dict(T=[5, 15])):
        with pytest.raises(ValueError, match="per-replica"):
            TMService(cfg, init_state(cfg),
                      ServiceConfig(replicas=4, **bad))


def test_service_requires_eval_set_for_analysis():
    svc = _make_service()
    with pytest.raises(ValueError):
        svc.analyze()
    # but tick without an eval set is a plain drain (no analysis)
    rep = svc.tick(2)
    assert rep.accuracy is None
    assert isinstance(svc.policy, AdaptPolicy)
    assert jnp.ndim(svc.rt.s) == 0


# ---------------------------------------------------------------------------
# The flush kernel: one dense rotated write per ring, bitwise the old scan.

# Per lane: head and size as fractions of the ring, and the rows staged.
# An empty ring, a block that wraps, a full ring, a lane with nothing
# staged (its rows all zero), a nearly full ring, one row, B - 1 rows into
# a half-full ring.
_LANES = [(0, 0, "B"), (0.9, 0.1, "B"), (0.5, 1.0, "B"), (0.3, 0.2, 0),
          (0.6, 0.9, "B"), (0.99, 0.0, 1), (0.2, 0.5, "B-1")]


def _lane_state(cap, block, head, size, count):
    return (min(int(head * cap), cap - 1), int(size * cap),
            {"B": block, "B-1": block - 1}.get(count, count))


@pytest.mark.parametrize("packed", [True, False], ids=["uint32", "bool"])
@pytest.mark.parametrize("cap,block", [(6, 3), (7, 7), (8, 4), (64, 32)])
def test_enqueue_rows_equals_per_row_pushes(cap, block, packed):
    """``_enqueue_rows`` over K lanes leaves every ring, and reports every
    accepted count, bitwise as a loop of single-row pushes per lane."""
    n_lanes = len(_LANES)
    cfg = TMConfig(n_features=F, max_classes=3, max_clauses=16, n_states=16)
    svc = TMService(cfg, init_state(cfg), ServiceConfig(
        replicas=n_lanes, buffer_capacity=cap, chunk=CHUNK,
        ingress_block=block, s=3.0, T=15, seed=0, packed=packed,
    ))
    rng = np.random.default_rng(cap * 100 + block)
    buf = svc.ss.buf
    width = buf.data_x.shape[-1]
    if packed:
        data_x = rng.integers(0, 2**32, (n_lanes, cap, width),
                              dtype=np.uint32)
        xs = rng.integers(0, 2**32, (n_lanes, block, width), dtype=np.uint32)
    else:
        data_x = rng.random((n_lanes, cap, width)) < 0.5
        xs = rng.random((n_lanes, block, width)) < 0.5
    ys = rng.integers(0, 3, (n_lanes, block), dtype=np.int32)
    states = [_lane_state(cap, block, *lane) for lane in _LANES]
    heads, sizes, counts = (np.asarray(v, np.int32) for v in zip(*states))
    xs[counts == 0] = 0
    ys[counts == 0] = 0
    ss = svc.ss._replace(buf=buf._replace(
        data_x=jnp.asarray(data_x),
        data_y=jnp.asarray(rng.integers(0, 3, (n_lanes, cap), np.int32)),
        head=jnp.asarray(heads), size=jnp.asarray(sizes),
    ))
    got, accepted = router_mod._enqueue_rows(ss, block, xs, ys, counts)
    assert np.asarray(accepted).dtype == np.int32
    for r in range(n_lanes):
        ring = jax.tree.map(lambda a: a[r], ss.buf)
        want = 0
        for i in range(int(counts[r])):
            ring, ok = buffer.push(ring, jnp.asarray(xs[r, i]),
                                   jnp.asarray(ys[r, i]))
            want += int(ok)
        assert int(accepted[r]) == want
        for g, w in zip(got.buf, ring):
            g, w = np.asarray(g)[r], np.asarray(w)
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)
    # the rest of the session state is left as it was
    for g, w in zip(jax.tree.leaves(got.tm), jax.tree.leaves(ss.tm)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _primitives(jaxpr, out):
    """Every primitive of a jaxpr, recursing into its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    _primitives(sub.jaxpr, out)
    return out


@pytest.mark.parametrize("packed", [True, False], ids=["uint32", "bool"])
def test_enqueue_rows_has_no_loop_scatter_or_dynamic_update(packed):
    """The flush is dense: its jaxpr holds no loop, no scatter and no
    dynamic update (under vmap those compile to per-tenant loops)."""
    svc = _make_packed_service() if packed else _make_service()
    ss = svc.ss
    xs = np.zeros((K, BLOCK) + ss.buf.data_x.shape[2:],
                  ss.buf.data_x.dtype)
    jaxpr = jax.make_jaxpr(router_mod._enqueue_rows, static_argnums=1)(
        ss, BLOCK, xs, np.zeros((K, BLOCK), np.int32),
        np.zeros(K, np.int32))
    prims = _primitives(jaxpr.jaxpr, set())
    assert "jit" in prims and "select_n" in prims   # the recursion reached
    banned = {"scan", "while", "scatter", "scatter-add", "scatter_add",
              "dynamic_update_slice"}
    assert not prims & banned, sorted(prims & banned)
