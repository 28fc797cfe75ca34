"""OnlineFleet: replica-parallel online serving (repro.serve.fleet).

The fleet's contract is *bit-exactness*: replica r of an ``OnlineFleet(K)``
must reproduce a standalone ``OnlineSession`` given the same RNG key and
offer stream — drained TA banks, monitoring aux and inference alike — on
both kernel backends. The mesh cases additionally pin that sharding the
replica axis over a device mesh changes nothing (they run on whatever
devices exist; CI re-runs them under a forced 4-host-device topology,
see .github/workflows/ci.yml `multidevice`).
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TMConfig, init_runtime, init_state
from repro.core.online import OnlineSession
from repro.data import iris
from repro.serve.fleet import OnlineFleet


def _cfg(backend="ref"):
    return TMConfig(n_features=16, max_classes=3, max_clauses=16,
                    n_states=16, backend=backend)


def _offer_streams(K, n, stride=7):
    """Distinct per-replica offer streams over the iris rows."""
    xs, ys = iris.load()
    return [
        [(xs[(i + stride * r) % len(xs)], int(ys[(i + stride * r) % len(xs)]))
         for i in range(n)]
        for r in range(K)
    ]


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("K", [1, 3, 8])
def test_fleet_drain_bitwise_identical_to_sessions(K, backend):
    """OnlineFleet(K) == K independent OnlineSessions, bit for bit."""
    cfg = _cfg(backend)
    rt = init_runtime(cfg, s=3.0, T=15)
    seeds = [100 + r for r in range(K)]
    streams = _offer_streams(K, 20)

    sessions = [
        OnlineSession(cfg, init_state(cfg), rt, buffer_capacity=32,
                      chunk=8, seed=seeds[r])
        for r in range(K)
    ]
    fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                        buffer_capacity=32, chunk=8, seed=seeds)

    for i in range(20):
        for r in range(K):
            x, y = streams[r][i]
            assert sessions[r].offer(x, y)
            assert fleet.offer(r, x, y)

    want_trained = [s.learn_available(20) for s in sessions]
    got_trained = fleet.drain(20)
    assert list(got_trained) == want_trained == [20] * K

    want = np.stack([np.asarray(s.ss.tm.ta_state) for s in sessions])
    np.testing.assert_array_equal(want, np.asarray(fleet.ss.tm.ta_state))

    # fleet inference == per-session inference (one fused contraction)
    xs, _ = iris.load()
    preds = fleet.infer(xs[:12])
    for r in range(K):
        np.testing.assert_array_equal(preds[r], sessions[r].infer(xs[:12]))


def test_fleet_uneven_streams_and_budgets_match_sessions():
    """Replicas that exhaust their buffer or budget early retire exactly
    like standalone sessions (no RNG burn, bitwise state parity), across
    multiple drain rounds."""
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    K = 3
    seeds = [7, 8, 9]
    counts = [5, 16, 11]          # uneven buffered rows per replica
    budgets = [3, 30, 11]         # uneven per-replica drain budgets
    streams = _offer_streams(K, max(counts))

    sessions = [
        OnlineSession(cfg, init_state(cfg), rt, buffer_capacity=32,
                      chunk=4, seed=seeds[r])
        for r in range(K)
    ]
    fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                        buffer_capacity=32, chunk=4, seed=seeds)
    for r in range(K):
        for i in range(counts[r]):
            sessions[r].offer(*streams[r][i])
            fleet.offer(r, *streams[r][i])

    want = [sessions[r].learn_available(budgets[r]) for r in range(K)]
    got = fleet.drain(np.asarray(budgets))
    assert list(got) == want == [3, 16, 11]

    # second round: offer more and drain again — RNG streams must still agree
    for r in range(K):
        for i in range(4):
            sessions[r].offer(*streams[r][i])
            fleet.offer(r, *streams[r][i])
    want2 = [sessions[r].learn_available(10) for r in range(K)]
    got2 = fleet.drain(10)
    assert list(got2) == want2
    want_ta = np.stack([np.asarray(s.ss.tm.ta_state) for s in sessions])
    np.testing.assert_array_equal(want_ta, np.asarray(fleet.ss.tm.ta_state))
    np.testing.assert_array_equal(
        fleet.buffered, [s.buffered for s in sessions]
    )


def test_fleet_per_replica_hyperparameters_match_sessions():
    """rt.s/T as [K] vectors: every member learns under its own (s, T),
    bit-identical to sessions with those scalar runtimes."""
    cfg = _cfg()
    K = 3
    s_vals, T_vals = [1.375, 3.0, 5.0], [5, 15, 10]
    seeds = [41, 42, 43]
    streams = _offer_streams(K, 16)

    sessions = [
        OnlineSession(cfg, init_state(cfg),
                      init_runtime(cfg, s=s_vals[r], T=T_vals[r]),
                      buffer_capacity=32, chunk=8, seed=seeds[r])
        for r in range(K)
    ]
    rt = init_runtime(cfg)._replace(
        s=jnp.asarray(s_vals, jnp.float32), T=jnp.asarray(T_vals, jnp.int32)
    )
    fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                        buffer_capacity=32, chunk=8, seed=seeds)
    for i in range(16):
        for r in range(K):
            sessions[r].offer(*streams[r][i])
            fleet.offer(r, *streams[r][i])
    for s in sessions:
        s.learn_available(16)
    fleet.drain(16)
    want = np.stack([np.asarray(s.ss.tm.ta_state) for s in sessions])
    np.testing.assert_array_equal(want, np.asarray(fleet.ss.tm.ta_state))


def test_fleet_monitoring_aux_matches_sessions():
    """drain(on_chunk=) surfaces ChunkAux with leading [K] — bitwise equal
    to each session's per-chunk aux, and compiled out when absent."""
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    K = 3
    seeds = [1, 2, 3]
    streams = _offer_streams(K, 12)

    per_session: list = []
    sessions = []
    for r in range(K):
        s = OnlineSession(cfg, init_state(cfg), rt, buffer_capacity=32,
                          chunk=4, seed=seeds[r])
        sessions.append(s)
    fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                        buffer_capacity=32, chunk=4, seed=seeds)
    for i in range(12):
        for r in range(K):
            sessions[r].offer(*streams[r][i])
            fleet.offer(r, *streams[r][i])

    for r in range(K):
        chunks: list = []
        sessions[r].learn_available(12, on_chunk=chunks.append)
        per_session.append(chunks)
    fleet_chunks: list = []
    fleet.drain(12, on_chunk=fleet_chunks.append)

    assert len(fleet_chunks) == len(per_session[0]) == 3  # 12 points / chunk 4
    for c, fc in enumerate(fleet_chunks):
        for r in range(K):
            want = per_session[r][c]
            got = jax.tree.map(lambda a: np.asarray(a)[r], fc)
            for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(w), g)

    # without the hook, monitoring is compiled out and state is unchanged
    fleet2 = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                         buffer_capacity=32, chunk=4, seed=seeds)
    for i in range(12):
        for r in range(K):
            fleet2.offer(r, *streams[r][i])
    fleet2.drain(12)
    np.testing.assert_array_equal(
        np.asarray(fleet.ss.tm.ta_state), np.asarray(fleet2.ss.tm.ta_state)
    )


def test_fleet_backpressure_counts():
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=2,
                        buffer_capacity=4, chunk=2, seed=0)
    xs, ys = iris.load()
    for i in range(4):
        assert fleet.offer(0, xs[i], int(ys[i]))
    assert not fleet.offer(0, xs[4], int(ys[4]))   # replica 0 full
    assert fleet.offer(1, xs[4], int(ys[4]))       # replica 1 untouched
    np.testing.assert_array_equal(fleet.dropped, [1, 0])
    np.testing.assert_array_equal(fleet.buffered, [4, 1])


def test_fleet_adapt_manager_per_replica_rollback():
    """TMFleetAdaptManager: a member whose accuracy collapses rolls back to
    ITS known-good bank; healthy members keep serving untouched."""
    from repro.core.tm import TMState
    from repro.serve.online_adapt import TMFleetAdaptManager, TMOnlineAdaptConfig

    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    K = 3
    m = TMFleetAdaptManager(
        cfg, init_state(cfg), rt, xs[100:], ys[100:], n_replicas=K,
        oc=TMOnlineAdaptConfig(analyze_every=4, rollback_threshold=0.1,
                               buffer_capacity=16, chunk=4),
        seed=[5, 6, 7],
    )
    base = m.offline_train(xs[:80], ys[:80], n_epochs=10)
    assert base.shape == (K,)

    # Poison replica 0's TA bank (simulate corruption / bad adaptation):
    # next analysis must roll ONLY replica 0 back to its known-good bank.
    poisoned = np.asarray(m.fleet.ss.tm.ta_state).copy()
    poisoned[0] = np.asarray(init_state(cfg).ta_state)
    m.fleet.ss = m.fleet.ss._replace(
        tm=TMState(ta_state=jnp.asarray(poisoned))
    )
    accs = None
    for i in range(4):   # analyze_every=4 points per replica
        accs = m.observe_rows(np.asarray(xs[80 + i]), int(ys[80 + i]))
    assert accs is not None
    np.testing.assert_array_equal(m.rollbacks, [1, 0, 0])
    # replica 0's bank was restored BEFORE the post-rollback online points…
    assert float(m.analyze()[0]) >= float(base[0]) - 0.1
    # …and healthy replicas were never rolled back
    assert m.history[-1][1].shape == (K,)


def test_fleet_adapt_manager_per_replica_cadence():
    """Per-replica analysis counters: only members fed enough traffic hit
    their cadence; their counters reset independently."""
    from repro.serve.online_adapt import TMFleetAdaptManager, TMOnlineAdaptConfig

    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    xs, ys = iris.load()
    K = 3
    m = TMFleetAdaptManager(
        cfg, init_state(cfg), rt, xs[100:], ys[100:], n_replicas=K,
        oc=TMOnlineAdaptConfig(analyze_every=3, rollback_threshold=0.5,
                               buffer_capacity=16, chunk=4),
        seed=0,
    )
    m.offline_train(xs[:40], ys[:40], n_epochs=2)
    mask = np.array([True, True, False])   # starve replica 2
    out = None
    for i in range(3):
        out = m.observe_rows(np.asarray(xs[i]), int(ys[i]), mask)
    assert out is not None                  # replicas 0/1 hit cadence
    np.testing.assert_array_equal(m._since, [0, 0, 0])  # 2 never consumed
    # starved member then fed alone: fires after ITS OWN 3 points
    mask2 = np.array([False, False, True])
    assert m.observe_rows(np.asarray(xs[3]), int(ys[3]), mask2) is None
    assert m.observe_rows(np.asarray(xs[4]), int(ys[4]), mask2) is None
    assert m.observe_rows(np.asarray(xs[5]), int(ys[5]), mask2) is not None


# ---------------------------------------------------------------------------
# Hypothesis: arbitrary offer/drain/infer interleavings keep invariants.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dev dependency (requirements-dev.txt)
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    _ops = st.lists(
        st.one_of(
            st.tuples(st.just("offer"), st.integers(0, 2), st.integers(0, 149)),
            st.tuples(st.just("drain"), st.integers(0, 12), st.just(0)),
            st.tuples(st.just("infer"), st.just(0), st.just(0)),
        ),
        max_size=25,
    )

    @settings(max_examples=15, deadline=None)
    @given(ops_seq=_ops, seed=st.integers(0, 2**31 - 1))
    def test_fleet_interleaving_invariants(ops_seq, seed):
        """Any interleaving of offer/drain/infer across replicas keeps
        per-replica buffer counts in sync with a host-side FIFO model, the
        TA plane at its int8 dtype, and every state in [1, 2N] (the
        hardware's [-N, N) counter range shifted to 1-based)."""
        cfg = _cfg()
        cap, K = 6, 3
        rt = init_runtime(cfg, s=3.0, T=15)
        fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                            buffer_capacity=cap, chunk=4, seed=seed)
        xs, ys = iris.load()
        counts = [0] * K
        dtype0 = np.asarray(fleet.ss.tm.ta_state).dtype
        assert dtype0 == np.int8
        for op, a, b in ops_seq:
            if op == "offer":
                ok = fleet.offer(a, xs[b], int(ys[b]))
                assert ok == (counts[a] < cap)
                if counts[a] < cap:
                    counts[a] += 1
            elif op == "drain":
                trained = fleet.drain(a)
                for r in range(K):
                    assert trained[r] == min(a, counts[r])
                    counts[r] -= int(trained[r])
            else:
                preds = fleet.infer(xs[:5])
                assert preds.shape == (K, 5)
                assert ((preds >= 0) & (preds < cfg.max_classes)).all()
            np.testing.assert_array_equal(fleet.buffered, counts)
            ta = np.asarray(fleet.ss.tm.ta_state)
            assert ta.dtype == dtype0
            assert ta.min() >= 1 and ta.max() <= 2 * cfg.n_states


# ---------------------------------------------------------------------------
# Mesh cases (run on whatever devices exist; the CI `multidevice` job forces
# XLA_FLAGS=--xla_force_host_platform_device_count=4 so they exercise a real
# 4-device sharding of the replica axis).
# ---------------------------------------------------------------------------


def _data_mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), ("data",))


def test_fleet_mesh_sharded_bitwise_equal_to_unsharded():
    """Sharding the fleet's replica axis over the mesh changes nothing:
    drained TA banks and inference are bitwise equal to the local fleet."""
    cfg = _cfg()
    rt = init_runtime(cfg, s=3.0, T=15)
    K = 8  # divisible by 1, 2, 4 devices
    seeds = list(range(K))
    streams = _offer_streams(K, 12)

    runs = []
    for mesh in (None, _data_mesh()):
        fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=K,
                            buffer_capacity=16, chunk=4, seed=seeds,
                            mesh=mesh)
        for i in range(12):
            for r in range(K):
                fleet.offer(r, *streams[r][i])
        trained = fleet.drain(12)
        assert list(trained) == [12] * K
        runs.append((np.asarray(fleet.ss.tm.ta_state),
                     fleet.infer(iris.load()[0][:10])))
    np.testing.assert_array_equal(runs[0][0], runs[1][0])
    np.testing.assert_array_equal(runs[0][1], runs[1][1])


def test_replica_shardings_grid_major_device_local():
    """With n_replicas pinned, ONLY the full-R (grid-major) axis shards;
    per-data-stream leaves (D < R, even when divisible) replicate onto all
    devices so the kernels' r % D gather never crosses devices."""
    from jax.sharding import PartitionSpec as PS

    from repro.distributed import sharding as shard_mod

    mesh = _data_mesh()
    n_dev = len(jax.devices())
    R = 8 * n_dev
    tree = {
        "state": jax.ShapeDtypeStruct((R, 3, 16, 32), jnp.int8),   # full R
        "stream": jax.ShapeDtypeStruct((R // 2, 30, 16), bool),    # D | R
        "keys": jax.ShapeDtypeStruct((R // 2, 2), jnp.uint32),     # D | R
        "scalar": jax.ShapeDtypeStruct((), jnp.float32),
    }
    sh = shard_mod.replica_shardings(tree, mesh, n_replicas=R)
    assert sh["state"].spec == PS("data")
    assert sh["stream"].spec == PS()   # replicated: gather stays local
    assert sh["keys"].spec == PS()
    assert sh["scalar"].spec == PS()
    # the old no-n_replicas form guessed by divisibility — exactly the
    # D | R stream scattering the grid-major rule exists to prevent —
    # and is now a hard error (deprecated through PR 8)
    with pytest.raises(TypeError, match="n_replicas"):
        shard_mod.replica_shardings(tree, mesh)


def test_crossval_mesh_sharded_sweep_bitwise_equal():
    """CrossValRun(mesh=...) on however many devices exist == meshless run
    (the 4-device variant is what the multidevice CI job pins)."""
    from repro.data import blocks
    from repro.eval.crossval import CrossValRun

    cfg = _cfg()
    osets, _ = blocks.iris_paper_sets(n_orderings=4)
    kw = dict(n_epochs=3, seed=0)
    base = CrossValRun(cfg).sweep(
        osets.offline_x, osets.offline_y,
        osets.validation_x, osets.validation_y,
        (1.375, 3.0), (5, 15), **kw,
    )  # R = 2*2*4 = 16: divisible by 1/2/4 devices
    sharded = CrossValRun(cfg, mesh=_data_mesh()).sweep(
        osets.offline_x, osets.offline_y,
        osets.validation_x, osets.validation_y,
        (1.375, 3.0), (5, 15), **kw,
    )
    np.testing.assert_array_equal(
        np.asarray(base.val_accuracy), np.asarray(sharded.val_accuracy)
    )


FORCED_MESH_SCRIPT = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as PS
    assert len(jax.devices()) == 4, jax.devices()

    from repro.core import TMConfig, init_runtime, init_state
    from repro.data import blocks, iris
    from repro.distributed import sharding as shard_mod
    from repro.eval.crossval import CrossValRun
    from repro.serve.fleet import OnlineFleet

    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=16)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    # grid-major axis device-local: full-R leaves shard, D-streams replicate
    sh = shard_mod.replica_shardings(
        {"ta": jax.ShapeDtypeStruct((16, 3, 16, 32), jnp.int8),
         "stream": jax.ShapeDtypeStruct((4, 30, 16), bool)},
        mesh, n_replicas=16)
    assert sh["ta"].spec == PS("data"), sh["ta"]
    assert sh["stream"].spec == PS(), sh["stream"]

    # mesh-sharded sweep == single-device sweep, bitwise
    osets, _ = blocks.iris_paper_sets(n_orderings=4)
    kw = dict(n_epochs=3, seed=0)
    args = (osets.offline_x, osets.offline_y,
            osets.validation_x, osets.validation_y, (1.375, 3.0), (5, 15))
    base = CrossValRun(cfg).sweep(*args, **kw)
    sharded = CrossValRun(cfg, mesh=mesh).sweep(*args, **kw)
    np.testing.assert_array_equal(
        np.asarray(base.val_accuracy), np.asarray(sharded.val_accuracy))

    # mesh-sharded fleet == single-device fleet, bitwise
    xs, ys = iris.load()
    rt = init_runtime(cfg, s=3.0, T=15)
    tas = []
    for m in (None, mesh):
        fleet = OnlineFleet(cfg, init_state(cfg), rt, n_replicas=8,
                            buffer_capacity=16, chunk=4,
                            seed=list(range(8)), mesh=m)
        for i in range(8):
            fleet.offer_rows(
                np.stack([xs[(i + 7 * r) % 150] for r in range(8)]),
                np.asarray([int(ys[(i + 7 * r) % 150]) for r in range(8)]))
        fleet.drain(8)
        tas.append(np.asarray(fleet.ss.tm.ta_state))
    np.testing.assert_array_equal(tas[0], tas[1])
    print("OK")
""")


def test_forced_4_device_mesh_subprocess():
    """Sweep + fleet on a forced 4-host-device mesh are bitwise equal to
    the 1-device runs (subprocess: XLA device count is fixed at import)."""
    import os

    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run([sys.executable, "-c", FORCED_MESH_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


FORCED_MESH_PALLAS_SCRIPT = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from jax.sharding import Mesh
    assert len(jax.devices()) == 4, jax.devices()

    from repro.core import TMConfig, init_state
    from repro.data import blocks, iris
    from repro.eval.crossval import CrossValRun
    from repro.serve import ServiceConfig, TMService

    cfg = TMConfig(n_features=16, max_classes=3, max_clauses=16, n_states=16,
                   backend="pallas")
    mesh = Mesh(np.array(jax.devices()), ("data",))

    # sweeps over D = 4 orderings: R = 16 puts 4 replicas on each device
    # (the r % D rule holds in place), R = 8 puts 2 (each shard gathers
    # its own data streams)
    osets, _ = blocks.iris_paper_sets(n_orderings=4)
    for T in ((5, 15), (15,)):
        args = (osets.offline_x, osets.offline_y, osets.validation_x,
                osets.validation_y, (1.375, 3.0), T)
        base = CrossValRun(cfg).sweep(*args, n_epochs=2, seed=0)
        sharded = CrossValRun(cfg, mesh=mesh).sweep(*args, n_epochs=2, seed=0)
        np.testing.assert_array_equal(
            np.asarray(base.val_accuracy), np.asarray(sharded.val_accuracy))

    # packed fleet: offline training, drain, analysis and serving
    xs, ys = iris.load()
    outs = []
    for m in (None, mesh):
        svc = TMService(cfg, init_state(cfg), ServiceConfig(
            replicas=8, buffer_capacity=16, chunk=4, packed=True, s=3.0,
            T=15, seed=list(range(8)), mesh=m), eval_x=xs[:30], eval_y=ys[:30])
        svc.offline_train(xs[:20], ys[:20], n_epochs=1)
        for i in range(8):
            idx = (i + 7 * np.arange(8)) % 150
            svc.submit_rows(xs[idx], ys[idx])
        svc.tick(8)
        outs.append((np.asarray(svc.ss.tm.ta_state), svc.serve(xs[:10]),
                     svc.analyze()))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
    print("OK")
""")


def test_forced_4_device_mesh_pallas_subprocess():
    """The Pallas kernels on a forced 4-host-device mesh (each kernel runs
    per device under shard_map): sweeps and a packed fleet are bitwise
    equal to the 1-device runs."""
    import os

    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run([sys.executable, "-c", FORCED_MESH_PALLAS_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
