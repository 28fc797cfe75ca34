"""TMService redesign parity suite: shims == pre-redesign code, bit for bit.

``OnlineSession``, ``OnlineFleet``, ``TMOnlineAdaptManager`` and
``TMFleetAdaptManager`` are now thin shims over ``repro.serve.service.
TMService`` (one FSM, one drain, queue-based ingress). This suite pins
them to their PRE-redesign behavior: the ``Legacy*`` classes below are
faithful transcriptions of the deleted implementations (immediate
per-point device enqueue, per-object RNG key handling, duplicated
scalar / [K] FSMs), and every test drives shim and oracle through the
same traffic and asserts bitwise-identical trajectories — TA banks,
counters, monitoring aux, histories — on both kernel backends and for
K ∈ {1, 3, 8} including per-replica [K] s/T runtime ports.
"""
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TMConfig, init_runtime, init_state
from repro.core import accuracy as acc_mod
from repro.core import feedback as fb_mod
from repro.core import online as online_mod
from repro.core import tm as tm_mod
from repro.core.online import OnlineSession, SessionState
from repro.core.tm import TMState
from repro.data import buffer as buf_mod
from repro.data import iris
from repro.kernels import packing
from repro.serve.fleet import OnlineFleet
from repro.serve.online_adapt import (
    TMFleetAdaptManager,
    TMOnlineAdaptConfig,
    TMOnlineAdaptManager,
)


def _cfg(backend="ref"):
    return TMConfig(n_features=16, max_classes=3, max_clauses=16,
                    n_states=16, backend=backend)


def _offer_streams(K, n, stride=7):
    xs, ys = iris.load()
    return [
        [(xs[(i + stride * r) % len(xs)], int(ys[(i + stride * r) % len(xs)]))
         for i in range(n)]
        for r in range(K)
    ]


# ---------------------------------------------------------------------------
# Oracles: the pre-redesign implementations, transcribed verbatim (modulo
# imports). These are what the shims must reproduce bit for bit.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=0)
def _legacy_enqueue(cfg, ss, x, y):
    new_buf, ok = buf_mod.push(ss.buf, x, y)
    return ss._replace(buf=new_buf), ok


@partial(jax.jit, static_argnums=0)
def _legacy_enqueue_rows(cfg, ss, xs, ys, mask):
    def push_one(buf_r, x, y, m):
        new_buf, ok = buf_mod.push(buf_r, x, y)
        buf = jax.tree.map(lambda a, b: jnp.where(m, a, b), new_buf, buf_r)
        return buf, ok & m

    bufs, oks = jax.vmap(push_one)(ss.buf, xs, ys, mask)
    return ss._replace(buf=bufs), oks


@partial(jax.jit, static_argnums=(0, 1), static_argnames=("monitor",))
def _legacy_consume_many(cfg, k, ss, rt, limit, key, *, monitor=True):
    """The pre-redesign single-machine drain: up to ``min(k, limit,
    buffered)`` rows in one jitted scan of ``train_update``, monitoring
    hoisted into one batched inference pass over the chunk."""
    packed = ss.buf.data_x.dtype == jnp.uint32

    def body(carry, inp):
        buf, tm, n = carry
        i, kk = inp
        new_buf, x, y, nonempty = buf_mod.pop(buf)
        valid = (i < limit) & nonempty
        xb = packing.unpack_bits(x, cfg.n_features) if packed else x
        new_tm, _, activity = fb_mod.train_update(cfg, tm, rt, xb, y, kk)
        tm = jax.tree.map(lambda a, b: jnp.where(valid, a, b), new_tm, tm)
        buf = jax.tree.map(lambda a, b: jnp.where(valid, a, b), new_buf, buf)
        n = n + valid.astype(jnp.int32)
        return (buf, tm, n), (x, y, valid, jnp.where(valid, activity, 0.0))

    idx = jnp.arange(k, dtype=jnp.int32)
    (buf, tm, n), (xs, ys, valids, activity) = jax.lax.scan(
        body, (ss.buf, ss.tm, jnp.int32(0)), (idx, jax.random.split(key, k))
    )
    aux = None
    if monitor:
        preds = tm_mod.predict_batch_(cfg, tm, rt, xs)
        aux = online_mod.ChunkAux(
            predicted=preds.astype(jnp.int32),
            correct=(preds == ys) & valids,
            valid=valids,
            activity=activity,
        )
    return SessionState(tm=tm, buf=buf, step=ss.step + n), n, aux


@jax.jit
def _legacy_advance_keys(keys, active):
    k2 = jax.vmap(jax.random.split)(keys)
    return jnp.where(active[:, None], k2[:, 0], keys), k2[:, 1]


class LegacySession:
    """Pre-redesign OnlineSession: immediate enqueue, own scalar key."""

    def __init__(self, cfg, state, rt, *, buffer_capacity=64, chunk=16,
                 seed=0):
        self.cfg = cfg
        self.rt = rt
        self.chunk = max(1, min(chunk, buffer_capacity))
        self._key = jax.random.PRNGKey(seed)
        self.ss = SessionState(
            tm=state,
            buf=buf_mod.make(buffer_capacity, cfg.n_features),
            step=jnp.int32(0),
        )
        self.dropped = 0

    def offer(self, x, y) -> bool:
        x = jnp.asarray(x, dtype=bool)
        y = jnp.asarray(y, dtype=jnp.int32)
        self.ss, ok = _legacy_enqueue(self.cfg, self.ss, x, y)
        accepted = bool(ok)
        if not accepted:
            self.dropped += 1
        return accepted

    def learn_available(self, max_points, on_chunk=None) -> int:
        trained = 0
        monitor = on_chunk is not None
        while trained < max_points:
            want = min(self.chunk, max_points - trained)
            self._key, k = jax.random.split(self._key)
            self.ss, n, aux = _legacy_consume_many(
                self.cfg, self.chunk, self.ss, self.rt, jnp.int32(want), k,
                monitor=monitor,
            )
            n = int(n)
            trained += n
            if monitor and n:
                on_chunk(aux)
            if n < want:
                break
        return trained

    def infer(self, xs) -> np.ndarray:
        xs = jnp.asarray(xs, dtype=bool)
        return np.asarray(
            tm_mod.predict_batch(self.cfg, self.ss.tm, self.rt, xs)
        )

    @property
    def buffered(self) -> int:
        return int(self.ss.buf.size)


class LegacyFleet:
    """Pre-redesign OnlineFleet: one device dispatch per offered point."""

    def __init__(self, cfg, state, rt, *, n_replicas, buffer_capacity=64,
                 chunk=16, seed=0):
        if state.ta_state.ndim != 4:
            state = TMState(ta_state=jnp.broadcast_to(
                state.ta_state, (n_replicas,) + state.ta_state.shape
            ))
        self.cfg, self.rt = cfg, rt
        self.n_replicas = n_replicas
        self.chunk = max(1, min(chunk, buffer_capacity))
        if isinstance(seed, (int, np.integer)):
            base = jax.random.PRNGKey(int(seed))
            keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(
                jnp.arange(n_replicas)
            )
        else:
            keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seed])
        self._keys = keys
        K = n_replicas
        buf1 = buf_mod.make(buffer_capacity, cfg.n_features)
        bufs = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (K,) + a.shape), buf1
        )
        self.ss = SessionState(
            tm=state, buf=bufs, step=jnp.zeros((K,), jnp.int32)
        )
        self.dropped = np.zeros(K, dtype=np.int64)

    def offer_rows(self, xs, ys, mask=None) -> np.ndarray:
        K = self.n_replicas
        xs = jnp.broadcast_to(
            jnp.asarray(xs, dtype=bool), (K, self.cfg.n_features)
        )
        ys = jnp.broadcast_to(jnp.asarray(ys, dtype=jnp.int32), (K,))
        mask = (
            jnp.ones((K,), dtype=bool) if mask is None
            else jnp.asarray(mask, dtype=bool)
        )
        self.ss, oks = _legacy_enqueue_rows(self.cfg, self.ss, xs, ys, mask)
        accepted = np.asarray(oks)
        self.dropped += np.asarray(mask) & ~accepted
        return accepted

    def offer(self, r, x, y) -> bool:
        mask = np.zeros(self.n_replicas, dtype=bool)
        mask[r] = True
        return bool(self.offer_rows(x, y, mask)[r])

    def drain(self, max_points, on_chunk=None) -> np.ndarray:
        K = self.n_replicas
        budget = np.broadcast_to(
            np.asarray(max_points, dtype=np.int64), (K,)
        ).copy()
        trained = np.zeros(K, dtype=np.int64)
        active = trained < budget
        monitor = on_chunk is not None
        while active.any():
            want = np.where(
                active, np.minimum(self.chunk, budget - trained), 0
            ).astype(np.int32)
            self._keys, chunk_keys = _legacy_advance_keys(
                self._keys, jnp.asarray(active)
            )
            self.ss, n, aux = online_mod._consume_many_replicated(
                self.cfg, self.chunk, self.ss, self.rt,
                jnp.asarray(want), chunk_keys, monitor=monitor,
            )
            n = np.asarray(n, dtype=np.int64)
            trained += n
            if monitor and n.any():
                on_chunk(aux)
            active &= (n == want) & (trained < budget)
        return trained

    def infer(self, xs) -> np.ndarray:
        xs = jnp.asarray(xs, dtype=bool)
        if xs.ndim == 2:
            xs = xs[None]
        return np.asarray(tm_mod.predict_batch_replicated(
            self.cfg, self.ss.tm, self.rt, xs
        ))

    @property
    def buffered(self) -> np.ndarray:
        return np.asarray(self.ss.buf.size)

    @property
    def steps(self) -> np.ndarray:
        return np.asarray(self.ss.step)


class LegacyManager:
    """Pre-redesign TMOnlineAdaptManager: the scalar Fig-3 FSM."""

    def __init__(self, cfg, state, rt, eval_x, eval_y, oc=None, seed=0):
        self.cfg, self.rt = cfg, rt
        self.oc = oc or TMOnlineAdaptConfig()
        self.eval_x = jnp.asarray(eval_x, dtype=bool)
        self.eval_y = jnp.asarray(eval_y, dtype=jnp.int32)
        self.session = LegacySession(
            cfg, state, rt,
            buffer_capacity=self.oc.buffer_capacity,
            chunk=self.oc.chunk, seed=seed,
        )
        self.history: list = []
        self.rollbacks = 0
        self.lost = 0
        self._since_analysis = 0
        self._best: Optional[float] = None
        self._best_state = self.session.ss.tm

    def serve(self, xs) -> np.ndarray:
        return self.session.infer(xs)

    def analyze(self) -> float:
        acc = float(acc_mod.analyze(
            self.cfg, self.session.ss.tm, self.rt, self.eval_x, self.eval_y
        ))
        self.history.append((int(self.session.ss.step), acc))
        return acc

    def offline_train(self, xs, ys, n_epochs=10, seed=1) -> float:
        st = fb_mod.train_epochs(
            self.cfg, self.session.ss.tm, self.rt,
            jnp.asarray(xs, dtype=bool), jnp.asarray(ys, dtype=jnp.int32),
            jax.random.PRNGKey(seed), n_epochs,
        )
        self.session.ss = self.session.ss._replace(tm=st)
        acc = self.analyze()
        self._best, self._best_state = acc, st
        return acc

    def observe(self, x, y) -> Optional[float]:
        chunk = self.session.chunk
        if not self.session.offer(x, y):
            self._since_analysis += self.session.learn_available(chunk)
            if not self.session.offer(x, y):
                self.lost += 1
        self._since_analysis += self.session.learn_available(chunk)
        if self._since_analysis < self.oc.analyze_every:
            return None
        self._since_analysis = 0
        acc = self.analyze()
        if self._best is not None and acc < self._best - self.oc.rollback_threshold:
            self.session.ss = self.session.ss._replace(tm=self._best_state)
            self.rollbacks += 1
        elif self._best is None or acc > self._best:
            self._best, self._best_state = acc, self.session.ss.tm
        return acc


class LegacyFleetManager:
    """Pre-redesign TMFleetAdaptManager: the duplicated [K] Fig-3 FSM."""

    def __init__(self, cfg, state, rt, eval_x, eval_y, *, n_replicas,
                 oc=None, seed=0):
        self.cfg, self.rt = cfg, rt
        self.oc = oc or TMOnlineAdaptConfig()
        self.eval_x = jnp.asarray(eval_x, dtype=bool)
        self.eval_y = jnp.asarray(eval_y, dtype=jnp.int32)
        self.fleet = LegacyFleet(
            cfg, state, rt, n_replicas=n_replicas,
            buffer_capacity=self.oc.buffer_capacity,
            chunk=self.oc.chunk, seed=seed,
        )
        K = self.fleet.n_replicas
        self.history: list = []
        self.rollbacks = np.zeros(K, dtype=np.int64)
        self.lost = np.zeros(K, dtype=np.int64)
        self._since = np.zeros(K, dtype=np.int64)
        self._best = np.full(K, np.nan)
        self._best_state = self.fleet.ss.tm

    def serve(self, xs) -> np.ndarray:
        return self.fleet.infer(xs)

    def analyze(self) -> np.ndarray:
        acc = np.asarray(acc_mod.analyze_replicated(
            self.cfg, self.fleet.ss.tm, self.rt,
            self.eval_x[None], self.eval_y[None],
        ))
        self.history.append((self.fleet.steps, acc))
        return acc

    def offline_train(self, xs, ys, n_epochs=10, seed=1) -> np.ndarray:
        st = fb_mod.train_epochs_replicated(
            self.cfg, self.fleet.ss.tm, self.rt,
            jnp.asarray(xs, dtype=bool)[None],
            jnp.asarray(ys, dtype=jnp.int32)[None],
            jax.random.PRNGKey(seed)[None], n_epochs,
        )
        self.fleet.ss = self.fleet.ss._replace(tm=st)
        acc = self.analyze()
        self._best = acc.copy()
        self._best_state = st
        return acc

    def _select_rows(self, mask, new, old):
        gate = online_mod.replica_gate(jnp.asarray(mask))
        return jax.tree.map(gate, new, old)

    def observe_rows(self, xs, ys, mask=None) -> Optional[np.ndarray]:
        K = self.fleet.n_replicas
        mask = (
            np.ones(K, dtype=bool) if mask is None
            else np.asarray(mask, dtype=bool)
        )
        chunk = self.fleet.chunk
        accepted = self.fleet.offer_rows(xs, ys, mask)
        retry = mask & ~accepted
        if retry.any():
            self._since += self.fleet.drain(chunk)
            accepted = self.fleet.offer_rows(xs, ys, retry)
            self.lost += retry & ~accepted
        self._since += self.fleet.drain(chunk)

        due = self._since >= self.oc.analyze_every
        if not due.any():
            return None
        self._since[due] = 0
        acc = self.analyze()
        have_best = ~np.isnan(self._best)
        collapse = due & have_best & (
            acc < self._best - self.oc.rollback_threshold
        )
        improve = due & (~have_best | (acc > self._best))
        if collapse.any():
            self.fleet.ss = self.fleet.ss._replace(
                tm=self._select_rows(collapse, self._best_state,
                                     self.fleet.ss.tm)
            )
            self.rollbacks += collapse
        if improve.any():
            self._best = np.where(improve, acc, self._best)
            self._best_state = self._select_rows(
                improve, self.fleet.ss.tm, self._best_state
            )
        return acc

    def observe(self, r, x, y) -> Optional[np.ndarray]:
        mask = np.zeros(self.fleet.n_replicas, dtype=bool)
        mask[r] = True
        return self.observe_rows(x, y, mask)


# ---------------------------------------------------------------------------
# Session shim parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_session_shim_bitwise_matches_legacy(backend):
    """OnlineSession (a K = 1 TMService shim) == the pre-redesign session:
    offers incl. backpressure drops, chunked drains with monitoring aux,
    inference, step/buffered counters — identical trajectories."""
    cfg = _cfg(backend)
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()

    legacy = LegacySession(cfg, init_state(cfg), rt, buffer_capacity=16,
                           chunk=8, seed=11)
    shim = OnlineSession(cfg, init_state(cfg), rt, buffer_capacity=16,
                         chunk=8, seed=11)

    l_aux, s_aux = [], []
    for round_ in range(3):
        # overfill: the last 4 offers bounce off the full buffer
        for i in range(20):
            j = (round_ * 20 + i) % 150
            a = legacy.offer(xs[j], int(ys[j]))
            b = shim.offer(xs[j], int(ys[j]))
            assert a == b
        assert legacy.buffered == shim.buffered == 16
        assert legacy.dropped == shim.dropped
        budget = [13, 100, 16][round_]  # partial chunk / drain-to-empty
        nl = legacy.learn_available(budget, on_chunk=l_aux.append)
        ns = shim.learn_available(budget, on_chunk=s_aux.append)
        assert nl == ns
        np.testing.assert_array_equal(
            np.asarray(legacy.ss.tm.ta_state), np.asarray(shim.ss.tm.ta_state)
        )
        np.testing.assert_array_equal(legacy.infer(xs[:10]),
                                      shim.infer(xs[:10]))
    assert int(legacy.ss.step) == int(shim.ss.step)
    assert len(l_aux) == len(s_aux)
    for la, sa in zip(l_aux, s_aux):
        for w, g in zip(jax.tree.leaves(la), jax.tree.leaves(sa)):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_session_shim_state_swap_resyncs():
    """Replacing ``ss`` wholesale (the benchmarks' pre-fill pattern) keeps
    the shim's occupancy accounting exact."""
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    shim = OnlineSession(cfg, init_state(cfg), rt, buffer_capacity=8,
                         chunk=4, seed=0)
    filled = buf_mod.RingBuffer(
        data_x=jnp.asarray(xs[:8], dtype=bool),
        data_y=jnp.asarray(ys[:8], dtype=jnp.int32),
        head=jnp.int32(0), size=jnp.int32(8),
    )
    shim.ss = shim.ss._replace(buf=filled)
    assert shim.buffered == 8
    assert not shim.offer(xs[8], int(ys[8]))   # full: backpressure
    assert shim.learn_available(100) == 8
    assert shim.buffered == 0


# ---------------------------------------------------------------------------
# Fleet shim parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("K", [1, 3, 8])
def test_fleet_shim_bitwise_matches_legacy(K, backend):
    """OnlineFleet (a TMService shim with router ingress) == the
    pre-redesign fleet that dispatched every offer to the device."""
    cfg = _cfg(backend)
    rt = init_runtime(cfg, s=3.0, T=15)
    seeds = [50 + r for r in range(K)]
    streams = _offer_streams(K, 20)

    legacy = LegacyFleet(cfg, init_state(cfg), rt, n_replicas=K,
                         buffer_capacity=32, chunk=8, seed=seeds)
    shim = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                       buffer_capacity=32, chunk=8, seed=seeds)
    for i in range(20):
        for r in range(K):
            x, y = streams[r][i]
            assert legacy.offer(r, x, y)
            assert shim.offer(r, x, y)
    np.testing.assert_array_equal(legacy.buffered, shim.buffered)

    l_aux, s_aux = [], []
    nl = legacy.drain(20, on_chunk=l_aux.append)
    ns = shim.drain(20, on_chunk=s_aux.append)
    np.testing.assert_array_equal(nl, ns)
    np.testing.assert_array_equal(
        np.asarray(legacy.ss.tm.ta_state), np.asarray(shim.ss.tm.ta_state)
    )
    np.testing.assert_array_equal(legacy.steps, shim.steps)
    assert len(l_aux) == len(s_aux)
    for la, sa in zip(l_aux, s_aux):
        for w, g in zip(jax.tree.leaves(la), jax.tree.leaves(sa)):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    xs, _ = iris.load()
    np.testing.assert_array_equal(legacy.infer(xs[:12]), shim.infer(xs[:12]))


def test_fleet_shim_per_replica_hyperparameters_match_legacy():
    """[K]-vector s/T runtime ports through the shim == pre-redesign."""
    cfg = _cfg()
    K = 3
    s_vals, T_vals = [1.375, 3.0, 5.0], [5, 15, 10]
    seeds = [21, 22, 23]
    streams = _offer_streams(K, 16)
    rt = init_runtime(cfg)._replace(
        s=jnp.asarray(s_vals, jnp.float32), T=jnp.asarray(T_vals, jnp.int32)
    )
    legacy = LegacyFleet(cfg, init_state(cfg), rt, n_replicas=K,
                         buffer_capacity=32, chunk=8, seed=seeds)
    shim = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                       buffer_capacity=32, chunk=8, seed=seeds)
    for i in range(16):
        for r in range(K):
            legacy.offer(r, *streams[r][i])
            shim.offer(r, *streams[r][i])
    np.testing.assert_array_equal(legacy.drain(16), shim.drain(16))
    np.testing.assert_array_equal(
        np.asarray(legacy.ss.tm.ta_state), np.asarray(shim.ss.tm.ta_state)
    )


def test_fleet_shim_backpressure_and_masks_match_legacy():
    """Masked offers, uneven budgets and buffer-full drops through the
    router ingress reproduce the immediate-dispatch fleet exactly."""
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    K = 3
    streams = _offer_streams(K, 24)
    legacy = LegacyFleet(cfg, init_state(cfg), rt, n_replicas=K,
                         buffer_capacity=6, chunk=4, seed=[1, 2, 3])
    shim = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                       buffer_capacity=6, chunk=4, seed=[1, 2, 3])
    rng = np.random.default_rng(0)
    for i in range(24):
        mask = rng.random(K) < 0.7
        x, y = streams[0][i]
        np.testing.assert_array_equal(
            legacy.offer_rows(x, y, mask), shim.offer_rows(x, y, mask)
        )
        if i % 5 == 4:
            budgets = rng.integers(0, 7, K)
            np.testing.assert_array_equal(
                legacy.drain(budgets), shim.drain(budgets)
            )
    np.testing.assert_array_equal(legacy.dropped, shim.dropped)
    np.testing.assert_array_equal(legacy.buffered, shim.buffered)
    legacy.drain(10)
    shim.drain(10)
    np.testing.assert_array_equal(
        np.asarray(legacy.ss.tm.ta_state), np.asarray(shim.ss.tm.ta_state)
    )


# ---------------------------------------------------------------------------
# Manager shim parity (the collapsed FSM == both deleted FSMs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_single_manager_shim_bitwise_matches_legacy(backend):
    """TMOnlineAdaptManager == the deleted scalar FSM across offline
    training, clean + poisoned traffic, backpressure (tiny buffer) and
    §5.3.2 rollbacks — identical histories, counters and TA banks."""
    cfg = _cfg(backend)
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    oc = TMOnlineAdaptConfig(analyze_every=8, rollback_threshold=0.05,
                             buffer_capacity=8, chunk=8)
    legacy = LegacyManager(cfg, init_state(cfg), rt, xs[100:], ys[100:],
                           oc=oc, seed=5)
    shim = TMOnlineAdaptManager(cfg, init_state(cfg), rt, xs[100:], ys[100:],
                                oc=oc, seed=5)
    bl = legacy.offline_train(xs[:80], ys[:80], n_epochs=6)
    bs = shim.offline_train(xs[:80], ys[:80], n_epochs=6)
    assert bl == bs

    rng = np.random.default_rng(3)
    for i in range(60):
        j = i % 100
        y = int(ys[j]) if i % 3 else int(rng.integers(0, 3))  # drifted labels
        al = legacy.observe(xs[j], y)
        ash = shim.observe(xs[j], y)
        assert (al is None) == (ash is None)
        if al is not None:
            assert al == ash
    assert legacy.rollbacks == shim.rollbacks
    assert legacy.lost == shim.lost
    assert legacy.history == shim.history
    np.testing.assert_array_equal(
        np.asarray(legacy.session.ss.tm.ta_state),
        np.asarray(shim.session.ss.tm.ta_state),
    )
    np.testing.assert_array_equal(legacy.serve(xs[:10]), shim.serve(xs[:10]))


@pytest.mark.parametrize("K", [1, 3, 8])
def test_fleet_manager_shim_bitwise_matches_legacy(K):
    """TMFleetAdaptManager == the deleted [K] FSM: masked traffic,
    per-replica [K] s/T ports, per-replica cadence/rollback/snapshot."""
    cfg = _cfg()
    xs, ys = iris.load()
    rt = init_runtime(cfg)._replace(
        s=jnp.asarray(np.linspace(1.375, 5.0, K), jnp.float32),
        T=jnp.asarray(np.linspace(5, 15, K).astype(int), jnp.int32),
    )
    oc = TMOnlineAdaptConfig(analyze_every=6, rollback_threshold=0.05,
                             buffer_capacity=8, chunk=4)
    seeds = [30 + r for r in range(K)]
    legacy = LegacyFleetManager(cfg, init_state(cfg), rt, xs[100:], ys[100:],
                                n_replicas=K, oc=oc, seed=seeds)
    shim = TMFleetAdaptManager(cfg, init_state(cfg), rt, xs[100:], ys[100:],
                               n_replicas=K, oc=oc, seed=seeds)
    np.testing.assert_array_equal(
        legacy.offline_train(xs[:60], ys[:60], n_epochs=5),
        shim.offline_train(xs[:60], ys[:60], n_epochs=5),
    )

    rng = np.random.default_rng(7)
    for i in range(40):
        j = i % 100
        mask = rng.random(K) < 0.8
        y = int(ys[j]) if i % 3 else int(rng.integers(0, 3))  # drifted labels
        al = legacy.observe_rows(xs[j], y, mask)
        ash = shim.observe_rows(xs[j], y, mask)
        assert (al is None) == (ash is None)
        if al is not None:
            np.testing.assert_array_equal(al, ash)
    np.testing.assert_array_equal(legacy.rollbacks, shim.rollbacks)
    np.testing.assert_array_equal(legacy.lost, shim.lost)
    np.testing.assert_array_equal(legacy._since, shim._since)
    assert len(legacy.history) == len(shim.history)
    for (ls, la), (ss_, sa) in zip(legacy.history, shim.history):
        np.testing.assert_array_equal(ls, ss_)
        np.testing.assert_array_equal(la, sa)
    np.testing.assert_array_equal(
        np.asarray(legacy.fleet.ss.tm.ta_state),
        np.asarray(shim.fleet.ss.tm.ta_state),
    )
    np.testing.assert_array_equal(legacy.serve(xs[:10]), shim.serve(xs[:10]))


def test_manager_shim_without_offline_train_matches_legacy():
    """Cold-start managers (no offline_train): the first due analysis
    snapshots a best from the initial banks instead of crashing — same
    trajectory as the legacy FSM, which seeded _best_state in __init__."""
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    oc = TMOnlineAdaptConfig(analyze_every=4, rollback_threshold=0.1,
                             buffer_capacity=16, chunk=4)
    legacy = LegacyManager(cfg, init_state(cfg), rt, xs[100:], ys[100:],
                           oc=oc, seed=2)
    shim = TMOnlineAdaptManager(cfg, init_state(cfg), rt, xs[100:], ys[100:],
                                oc=oc, seed=2)
    for i in range(12):
        al = legacy.observe(xs[i], int(ys[i]))
        ash = shim.observe(xs[i], int(ys[i]))
        assert (al is None) == (ash is None)
        if al is not None:
            assert al == ash
    assert legacy.history == shim.history
    assert legacy.rollbacks == shim.rollbacks
    np.testing.assert_array_equal(
        np.asarray(legacy.session.ss.tm.ta_state),
        np.asarray(shim.session.ss.tm.ta_state),
    )


# ---------------------------------------------------------------------------
# The native surface: tick() and ingress-specific behavior
# ---------------------------------------------------------------------------


def test_service_tick_drives_cadence_and_rollback():
    """The native submit/tick loop runs the same §5.3.2 policy: a poisoned
    member rolls back to its known-good bank on its next due analysis."""
    from repro.serve import AdaptPolicy, ServiceConfig, TMService

    cfg = _cfg()
    xs, ys = iris.load()
    K = 3
    svc = TMService(
        cfg, init_state(cfg),
        ServiceConfig(replicas=K, buffer_capacity=16, chunk=4,
                      s=3.0, T=15, seed=[5, 6, 7],
                      policy=AdaptPolicy(analyze_every=4,
                                         rollback_threshold=0.1)),
        eval_x=xs[100:], eval_y=ys[100:],
    )
    base = svc.offline_train(xs[:80], ys[:80], n_epochs=10)
    assert base.shape == (K,)

    poisoned = np.asarray(svc.ss.tm.ta_state).copy()
    poisoned[0] = np.asarray(init_state(cfg).ta_state)
    svc.ss = svc.ss._replace(tm=TMState(ta_state=jnp.asarray(poisoned)))

    reports = []
    for i in range(4):
        svc.submit_rows(np.asarray(xs[80 + i]), int(ys[80 + i]))
        reports.append(svc.tick())
    assert all(r.trained.shape == (K,) for r in reports)
    fired = [r for r in reports if r.accuracy is not None]
    assert fired and fired[-1].rolled_back.tolist() == [True, False, False]
    np.testing.assert_array_equal(svc.rollbacks, [1, 0, 0])
    assert float(svc.analyze()[0]) >= float(base[0]) - 0.1


def test_service_ingress_is_batched_not_per_point():
    """The routed ingress path: N offers per replica cost O(N / B_ingress)
    device dispatches, not N — and the buffers still receive every row in
    order (the drained TA banks prove it: bitwise equal to the per-point
    legacy fleet)."""
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    K = 4
    streams = _offer_streams(K, 24)
    legacy = LegacyFleet(cfg, init_state(cfg), rt, n_replicas=K,
                         buffer_capacity=32, chunk=8, seed=list(range(K)))
    shim = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                       buffer_capacity=32, chunk=8, seed=list(range(K)))
    for i in range(24):
        for r in range(K):
            legacy.offer(r, *streams[r][i])
            shim.offer(r, *streams[r][i])
    # 96 offers; ingress_block=32 per replica -> exactly 1 auto-flush so far
    assert shim.service.router.flushes <= 2
    np.testing.assert_array_equal(legacy.drain(24), shim.drain(24))
    np.testing.assert_array_equal(
        np.asarray(legacy.ss.tm.ta_state), np.asarray(shim.ss.tm.ta_state)
    )
