"""Residency + durable state: K logical replicas on R device slots.

Three contracts (DESIGN.md §15), all bitwise:

* an evicted/reactivated replica's trajectory equals its always-resident
  twin's (the per-replica independence of the replicated drain makes the
  slot a replica sits in irrelevant);
* save -> restore -> continue equals never stopping (TA banks, RNG keys,
  ring buffers, policy FSM), packed and unpacked, both backends;
* no datapoint is lost or reordered per replica under arbitrary
  submit/tick/save/restore/evict/activate interleavings (extends the
  test_router.py FIFO-model property to the residency layer).

Plus the §5.3.2 regression the residency work surfaced: AdaptPolicy's
first due analysis with ``best_state=None`` (no offline-train baseline)
used to crash in ``_select_replicas`` with a pytree structure mismatch.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TMConfig, init_state
from repro.serve import AdaptPolicy, ServiceConfig, TMService

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dev dependency (requirements-dev.txt)
    HAVE_HYPOTHESIS = False

K, CAP, BLOCK, CHUNK, F = 6, 8, 4, 4, 16

_RNG = np.random.default_rng(42)
EVAL_X = _RNG.random((24, F)) > 0.5
EVAL_Y = _RNG.integers(0, 3, 24)


def _cfg(backend="ref"):
    return TMConfig(n_features=F, max_classes=3, max_clauses=16,
                    n_states=16, backend=backend)


def _service(resident=None, *, packed=False, backend="ref", seed=7,
             with_eval=True, analyze_every=8, batched=True):
    cfg = _cfg(backend)
    sc = ServiceConfig(
        replicas=K, buffer_capacity=CAP, chunk=CHUNK, ingress_block=BLOCK,
        packed=packed, s=3.0, T=15, seed=seed, resident=resident,
        batched_moves=batched,
        policy=AdaptPolicy(analyze_every=analyze_every,
                           rollback_threshold=0.1),
    )
    kw = (dict(eval_x=EVAL_X, eval_y=EVAL_Y) if with_eval else {})
    return TMService(cfg, init_state(cfg), sc, **kw)


def _drive(svc, n, seed, tick_every=4):
    r = np.random.default_rng(seed)
    for i in range(n):
        svc.submit_rows(r.random(F) > 0.5, int(r.integers(0, 3)))
        if i % tick_every == tick_every - 1:
            svc.tick()
    svc.flush()


def _state_leaves(svc):
    return [np.asarray(l)
            for l in jax.tree.leaves((svc.ss, svc.rng_keys, svc.steps,
                                      svc.since_analysis, svc.rollbacks))]


def _assert_same_state(a, b, msg=""):
    for la, lb in zip(_state_leaves(a), _state_leaves(b)):
        np.testing.assert_array_equal(la, lb, err_msg=msg)
    np.testing.assert_array_equal(a._ps.best, b._ps.best, err_msg=msg)


# ---------------------------------------------------------------------------
# The best_state=None first-due regression (§5.3.2 without a baseline)
# ---------------------------------------------------------------------------


def test_adapt_policy_first_due_without_baseline():
    """A policy initialized WITHOUT offline_train/snapshot (best_state is
    None) must survive its first due analysis: the first improve is an
    unconditional snapshot, not a _select_replicas pytree crash."""
    pol = AdaptPolicy(analyze_every=4, rollback_threshold=0.1)
    ps = pol.init(3)
    assert ps.best_state is None
    cfg = _cfg()
    tm = jax.tree.map(lambda a: jnp.broadcast_to(a, (3,) + a.shape),
                      init_state(cfg))
    ps.since[:] = 4
    due = pol.due(ps)
    assert due.all()
    acc = np.asarray([0.5, 0.4, 0.6], dtype=np.float32)
    tm2, rolled = pol.apply(ps, due, acc, tm)  # pre-fix: crashed here
    assert not rolled.any()
    np.testing.assert_array_equal(ps.best, acc.astype(np.float64))
    assert ps.best_state is not None
    # ... and the snapshot is live: a later collapse rolls back to it
    ps.since[:] = 4
    bad = np.asarray([0.1, 0.4, 0.6], dtype=np.float32)
    tm3, rolled = pol.apply(ps, pol.due(ps), bad, tm2)
    assert rolled.tolist() == [True, False, False]
    np.testing.assert_array_equal(
        np.asarray(tm3.ta_state[0]), np.asarray(ps.best_state.ta_state[0])
    )


def test_service_cold_start_first_due_analysis():
    """A fresh service (already-trained state handed in, never calling
    offline_train) ticks through its first due analysis without a
    baseline: best_state starts None and the first improve snapshots."""
    svc = _service(analyze_every=8)
    assert svc._ps.best_state is None
    reported = None
    r = np.random.default_rng(0)
    for i in range(24):
        svc.submit_rows(r.random(F) > 0.5, int(r.integers(0, 3)))
        rep = svc.tick()
        if rep.accuracy is not None:
            reported = rep
    assert reported is not None, "never reached a due analysis"
    assert svc._ps.best_state is not None
    assert not np.isnan(svc._ps.best).any()


# ---------------------------------------------------------------------------
# Residency: twin-bitwise, explicit evict/activate, serve_replicas
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("packed", [False, True])
def test_residency_twin_bitwise(packed):
    """K=6 on 2 slots vs an always-resident fleet driven with budgets
    masked by `buffered > 0` (the residency drain's sweep criterion):
    every replica's full trajectory — TA bank, RNG key, ring buffer,
    step, policy FSM — is bitwise identical, across many evictions."""
    twin = _service(None, packed=packed)
    res = _service(2, packed=packed)
    r = np.random.default_rng(3)
    for i in range(40):
        x, y = r.random(F) > 0.5, int(r.integers(0, 3))
        twin.submit_rows(x, y)
        res.submit_rows(x, y)
        if i % 4 == 3:
            res.flush()
            mask = res.buffered > 0
            res.tick()
            twin.tick(np.where(mask, twin.chunk, 0))
    assert res.obs.counters()["residency.evictions"] > 10, \
        "traffic never contended the slots"
    _assert_same_state(twin, res)
    # prediction parity on the (identical) per-replica states
    xs = _RNG.random((5, F)) > 0.5
    np.testing.assert_array_equal(
        twin.serve_replicas([0, 3, 5], xs), res.serve_replicas([0, 3, 5], xs)
    )


def test_explicit_evict_activate_roundtrip():
    svc = _service(3, with_eval=False)
    _drive(svc, 20, seed=5)
    before = _state_leaves(svc)
    buffered = svc.buffered.copy()
    svc.evict(np.arange(K))          # spill everything (<= R at a time)
    assert svc.resident.sum() == 0
    np.testing.assert_array_equal(svc.buffered, buffered)  # nothing lost
    svc.activate([4, 1, 0])
    assert set(np.nonzero(svc.resident)[0]) == {0, 1, 4}
    for la, lb in zip(before, _state_leaves(svc)):
        np.testing.assert_array_equal(la, lb)


def test_serve_replicas_matches_full_serve():
    svc = _service(None, with_eval=False)
    _drive(svc, 20, seed=9)
    xs = _RNG.random((7, F)) > 0.5
    full = svc.serve(xs)
    np.testing.assert_array_equal(svc.serve_replicas([5, 0, 2], xs),
                                  full[[5, 0, 2]])


def test_residency_rejects_wholesale_state_and_full_serve():
    svc = _service(2, with_eval=False)
    # the refusal must name BOTH ways out: serve_replicas for named
    # members, and the 'resident' knob to cover the fleet
    with pytest.raises(ValueError) as ei:
        svc.serve(_RNG.random((2, F)) > 0.5)
    assert "serve_replicas" in str(ei.value)
    assert "resident" in str(ei.value)
    with pytest.raises(ValueError, match="restore"):
        svc.ss = svc.ss
    with pytest.raises(ValueError, match="resident"):
        _service(2, with_eval=False).offline_train(EVAL_X, EVAL_Y, 1)
    with pytest.raises(ValueError, match="scalar s/T"):
        cfg = _cfg()
        TMService(cfg, init_state(cfg), ServiceConfig(
            replicas=K, resident=2, s=[3.0] * K, T=15, seed=0))


def test_residency_policy_rollback_matches_twin():
    """The §5.3.2 FSM under residency (host-side best banks) transitions
    identically to the always-resident policy, including rollbacks."""
    twin = _service(None, analyze_every=4)
    res = _service(2, analyze_every=4)
    r = np.random.default_rng(17)
    for i in range(60):
        x, y = r.random(F) > 0.5, int(r.integers(0, 3))
        twin.submit_rows(x, y)
        res.submit_rows(x, y)
        res.flush()
        mask = res.buffered > 0
        res.tick()
        twin.tick(np.where(mask, twin.chunk, 0))
    np.testing.assert_array_equal(twin.rollbacks, res.rollbacks)
    np.testing.assert_array_equal(twin._ps.since, res._ps.since)
    np.testing.assert_array_equal(twin._ps.best, res._ps.best)
    if twin._ps.best_state is not None:
        np.testing.assert_array_equal(
            np.asarray(twin._ps.best_state.ta_state), res._best_host
        )
    _assert_same_state(twin, res)


def test_sharded_residency_matches_unsharded_twin():
    """The resident plane sharded grid-major over whatever devices exist
    (the CI `multidevice` job forces 4 host devices) runs the full
    evict/activate lifecycle bitwise equal to an UNSHARDED always-
    resident fleet — extending the sharded-vs-1-device assertion to the
    residency layer."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()), ("data",))
    cfg = _cfg()
    sc = ServiceConfig(
        replicas=K, buffer_capacity=CAP, chunk=CHUNK, ingress_block=BLOCK,
        s=3.0, T=15, seed=7, resident=len(jax.devices()), mesh=mesh,
        policy=AdaptPolicy(analyze_every=8, rollback_threshold=0.1),
    )
    res = TMService(cfg, init_state(cfg), sc, eval_x=EVAL_X, eval_y=EVAL_Y)
    twin = _service(None)
    r = np.random.default_rng(3)
    for i in range(32):
        x, y = r.random(F) > 0.5, int(r.integers(0, 3))
        twin.submit_rows(x, y)
        res.submit_rows(x, y)
        if i % 4 == 3:
            res.flush()
            mask = res.buffered > 0
            res.tick()
            twin.tick(np.where(mask, twin.chunk, 0))
    assert res.obs.counters()["residency.evictions"] > 0
    _assert_same_state(twin, res)


# ---------------------------------------------------------------------------
# Batched moves (§17): multi-cohort superblocks, scoped evict, auto slots
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_multicohort_batched_matches_sync_oracle(backend, packed):
    """EVERY lane hot on 2 slots (hot-lane count = 3x resident, so each
    flush and each drain sweep runs 3 cohorts through the coalesced
    superblock path): the batched datapath — fused activate+enqueue,
    deferred spill settlement — lands bitwise on PR 8's synchronous
    per-cohort oracle AND on the always-resident twin."""
    batched = _service(2, packed=packed, backend=backend)
    oracle = _service(2, packed=packed, backend=backend, batched=False)
    twin = _service(None, packed=packed, backend=backend)
    assert batched._batched and not oracle._batched
    r = np.random.default_rng(11)
    for i in range(10):
        for _ in range(2):   # all K lanes hot every round
            x, y = r.random(F) > 0.5, int(r.integers(0, 3))
            for svc in (batched, oracle, twin):
                svc.submit_rows(x, y)
        for svc in (batched, oracle, twin):
            svc.tick(2)
    assert batched.obs.counters()["residency.evictions"] > 10, \
        "slots were never contended"
    _assert_same_state(oracle, batched, "batched diverged from oracle")
    _assert_same_state(twin, batched, "batched diverged from twin")


def test_scoped_evict_leaves_other_lanes_staged():
    """evict() lands ONLY the named replicas' staged rows (take_lanes),
    not the whole fleet's: other lanes stay staged (no block swap, no
    flush dispatch), and the evicted member's rows are in its spilled
    ring — nothing lost, nothing reordered."""
    svc = _service(2, with_eval=False)
    r = np.random.default_rng(2)
    for _ in range(3):
        svc.submit_rows(r.random(F) > 0.5, int(r.integers(0, 3)))
    staged_before = svc.router.staged
    assert (staged_before == 3).all()
    buffered_before = svc.buffered.copy()
    flushes_before = svc.router.flushes
    svc.evict([1])
    assert not svc.resident[1]
    staged = svc.router.staged
    assert staged[1] == 0, "the evicted lane must land before the spill"
    np.testing.assert_array_equal(
        staged[[0, 2, 3, 4, 5]], staged_before[[0, 2, 3, 4, 5]]
    )
    assert svc.router.flushes == flushes_before, "scoped path swapped a block"
    np.testing.assert_array_equal(svc.buffered, buffered_before)
    # the landed rows really are in the spilled snapshot's ring
    assert int(np.asarray(svc.ss.buf.size)[1]) == 3


def test_auto_resident_grow_shrink_trajectory():
    """resident='auto': dense traffic grows the plane (the EWMA active
    set no longer fits), sparse traffic shrinks it back through the
    hysteresis band — and the trajectory stays bitwise equal to the
    always-resident twin across every re-partition."""
    auto = _service("auto")
    twin = _service(None)
    assert auto.n_resident == 2        # ceil(K / 4) initial slots
    r = np.random.default_rng(23)

    def step(n_lanes):
        mask = np.zeros(K, dtype=bool)
        mask[:n_lanes] = True
        x, y = r.random(F) > 0.5, int(r.integers(0, 3))
        auto.submit_rows(x, y, mask)
        twin.submit_rows(x, y, mask)
        auto.flush()
        drive = auto.buffered > 0
        auto.tick()
        twin.tick(np.where(drive, twin.chunk, 0))

    for _ in range(8):
        step(K)                        # dense: every lane active
    grown = auto.n_resident
    assert grown > 2, "dense traffic never grew the plane"
    for _ in range(12):
        step(1)                        # sparse: one active lane
    assert auto.n_resident < grown, "sparse traffic never shrank the plane"
    assert auto.repartitions >= 2
    _assert_same_state(twin, auto, "trajectory changed across re-partitions")


def test_auto_resident_save_restore_continuation_bitwise(tmp_path):
    """save -> restore -> continue stays bitwise under resident='auto':
    the checkpoint is residency-agnostic, the restored service re-sizes
    on its own traffic, and neither side's trajectory moves."""
    svc = _service("auto")
    _drive(svc, 20, seed=5)
    svc.save(str(tmp_path))
    svc.load(str(tmp_path))
    other = TMService.restore(str(tmp_path), eval_x=EVAL_X, eval_y=EVAL_Y)
    assert other.sc.resident == "auto" and other._auto
    _assert_same_state(svc, other, "restore changed state")
    _drive(svc, 30, seed=11)
    _drive(other, 30, seed=11)
    _assert_same_state(svc, other, "post-restore trajectories diverged")


# ---------------------------------------------------------------------------
# Durable state: the save -> restore -> continue oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("resident", [None, 3])
def test_save_restore_continuation_bitwise(packed, resident, tmp_path):
    """save -> restore -> continue == never stopping, bitwise: TA banks,
    RNG keys, ring buffers, steps, policy FSM, analysis history."""
    svc = _service(resident, packed=packed)
    _drive(svc, 20, seed=5)
    svc.save(str(tmp_path))
    # realign the writer's residency partitioning with the reader's
    # (first-R resident — partitioning is NOT part of the logical state)
    svc.load(str(tmp_path))
    other = TMService.restore(str(tmp_path), eval_x=EVAL_X, eval_y=EVAL_Y)
    assert other.sc.packed == packed and other.sc.resident == resident
    _assert_same_state(svc, other, "restore changed state")
    assert len(other.history) == len(svc.history)
    _drive(svc, 30, seed=11)
    _drive(other, 30, seed=11)
    _assert_same_state(svc, other, "post-restore trajectories diverged")
    np.testing.assert_array_equal(svc.rollbacks, other.rollbacks)
    np.testing.assert_array_equal(svc.dropped, other.dropped)


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_save_restore_continuation_backends(backend, packed, tmp_path):
    """The round-trip oracle on both kernel backends, packed and
    unpacked (the pallas cases are what the kernels-pallas CI job pins):
    trajectories AND served predictions stay bitwise."""
    svc = _service(2, backend=backend, packed=packed)
    _drive(svc, 12, seed=5)
    svc.save(str(tmp_path))
    svc.load(str(tmp_path))
    other = TMService.restore(str(tmp_path), eval_x=EVAL_X, eval_y=EVAL_Y)
    _drive(svc, 12, seed=11)
    _drive(other, 12, seed=11)
    _assert_same_state(svc, other, f"{backend} restore diverged")
    xs = _RNG.random((4, F)) > 0.5
    np.testing.assert_array_equal(svc.serve_replicas(np.arange(K), xs),
                                  other.serve_replicas(np.arange(K), xs))


def test_restore_migrates_across_resident_budgets(tmp_path):
    """One checkpoint, any device budget: the assembled logical fleet is
    identical restored fully-resident, at R=1, or at the saved R."""
    svc = _service(3)
    _drive(svc, 25, seed=5)
    svc.save(str(tmp_path))
    restored = [TMService.restore(str(tmp_path), resident=r,
                                  eval_x=EVAL_X, eval_y=EVAL_Y)
                for r in (None, 1, 3)]
    assert [s.n_resident for s in restored] == [K, 1, 3]
    for other in restored[1:]:
        _assert_same_state(restored[0], other, "migration changed state")


def test_save_flushes_staged_ingress(tmp_path):
    """Rows staged but not yet flushed at save time are in the saved
    rings — a checkpoint never loses accepted traffic."""
    svc = _service(2, with_eval=False)
    svc.submit_rows(np.ones(F, dtype=bool), 1)
    assert svc.router.staged.sum() > 0 or svc.buffered.sum() > 0
    svc.save(str(tmp_path))
    other = TMService.restore(str(tmp_path))
    np.testing.assert_array_equal(other.buffered, [1] * K)


def test_fleet_save_restore_passthrough(tmp_path):
    """The OnlineFleet shim checkpoints and rebuilds through the service
    surface; continuation stays bitwise."""
    from repro.core import init_runtime
    from repro.serve import OnlineFleet

    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=4, seed=3)
    r = np.random.default_rng(0)
    for _ in range(10):
        fleet.offer_rows(r.random(F) > 0.5, int(r.integers(0, 3)))
        fleet.drain(2)
    fleet.save(str(tmp_path))
    other = OnlineFleet.restore(str(tmp_path))
    for _ in range(10):
        x, y = r.random(F) > 0.5, int(r.integers(0, 3))
        fleet.offer_rows(x, y)
        other.offer_rows(x, y)
        np.testing.assert_array_equal(fleet.drain(2), other.drain(2))
    for la, lb in zip(jax.tree.leaves(fleet.ss), jax.tree.leaves(other.ss)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_restore_rejects_mismatched_shape(tmp_path):
    svc = _service(2, with_eval=False)
    svc.save(str(tmp_path))
    wrong = _service(None, packed=True, with_eval=False)
    with pytest.raises(ValueError, match="packed"):
        wrong.load(str(tmp_path))


# ---------------------------------------------------------------------------
# Hypothesis: arbitrary interleavings (FIFO model + always-resident twin)
# ---------------------------------------------------------------------------


def _row(uid: int):
    x = np.array([(uid >> b) & 1 for b in range(F)], dtype=bool)
    return x, uid % 3


def _uid(x: np.ndarray) -> int:
    return int(sum(int(v) << b for b, v in enumerate(x)))


def _rings(svc):
    """Per-replica assembled ring content, oldest first, as uids."""
    buf = svc.ss.buf
    out = []
    for r in range(K):
        data_x = np.asarray(buf.data_x[r])
        head = int(np.asarray(buf.head[r]))
        size = int(np.asarray(buf.size[r]))
        out.append([_uid(data_x[(head + i) % CAP]) for i in range(size)])
    return out


class _Model:
    """Host-side reference: per-replica FIFO + conservation counters."""

    def __init__(self):
        self.queue = [[] for _ in range(K)]
        self.dropped = np.zeros(K, dtype=np.int64)

    def submit(self, uid, mask):
        ok = np.zeros(K, dtype=bool)
        for r in range(K):
            if not mask[r]:
                continue
            if len(self.queue[r]) >= CAP:
                self.dropped[r] += 1
            else:
                self.queue[r].append(uid)
                ok[r] = True
        return ok

    def drain(self, budget):
        for r in range(K):
            del self.queue[r][:min(int(budget[r]), len(self.queue[r]))]


if HAVE_HYPOTHESIS:
    _ops = st.lists(
        st.one_of(
            st.tuples(st.just("submit"), st.integers(1, 2 ** K - 1)),
            st.tuples(st.just("flush"), st.just(0)),
            st.tuples(st.just("tick"), st.integers(0, CHUNK)),
            st.tuples(st.just("evict"), st.integers(0, K - 1)),
            st.tuples(st.just("activate"), st.integers(0, K - 1)),
            st.tuples(st.just("saverestore"), st.just(0)),
        ),
        max_size=25,
    )

    @settings(max_examples=15, deadline=None)
    @given(ops_seq=_ops, seed=st.integers(0, 2 ** 31 - 1))
    def test_residency_interleavings_no_divergence_no_loss(ops_seq, seed):
        """Arbitrary submit/flush/tick/evict/activate/save/restore
        interleavings: (1) every replica's trajectory stays bitwise equal
        to its never-evicted twin's, (2) per-replica FIFO order and
        conservation hold on the assembled rings."""
        res = _service(2, seed=seed, with_eval=False)
        twin = _service(None, seed=seed, with_eval=False)
        model = _Model()
        uid = 0
        with tempfile.TemporaryDirectory() as ckdir:
            for op, arg in ops_seq:
                if op == "submit":
                    uid += 1
                    x, y = _row(uid)
                    mask = np.array([(arg >> r) & 1 for r in range(K)],
                                    dtype=bool)
                    got = res.submit_rows(x, y, mask)
                    np.testing.assert_array_equal(
                        got, twin.submit_rows(x, y, mask))
                    np.testing.assert_array_equal(
                        got, model.submit(uid, mask))
                elif op == "flush":
                    res.flush()
                    twin.flush()
                elif op == "tick":
                    res.flush()
                    twin.flush()
                    mask = res.buffered > 0
                    trained = res.tick(arg).trained
                    budget = np.where(mask, arg, 0)
                    np.testing.assert_array_equal(
                        trained, twin.tick(budget).trained)
                    model.drain(budget)
                elif op == "evict":
                    res.evict([arg])
                    twin.flush()  # evict flushes staged ingress first
                elif op == "activate":
                    res.activate([arg])
                else:  # saverestore: self round-trip mid-stream
                    res.save(ckdir)
                    res.load(ckdir)
                    twin.flush()  # save flushes staged ingress first
            np.testing.assert_array_equal(res.buffered, twin.buffered)
            np.testing.assert_array_equal(res.dropped, model.dropped)
            np.testing.assert_array_equal(
                res.buffered, [len(q) for q in model.queue])
            _assert_same_state(twin, res, "twin diverged")
            assert _rings(res) == model.queue, "ring diverged from FIFO"
