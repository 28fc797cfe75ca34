"""Online data manager + interleaved learning session (paper §3.5, §4).

The FPGA's online path: datapoints arrive from an application-dependent source,
pass through the cyclic buffer (so accuracy-analysis stalls never drop data),
and are consumed one per request by the TM manager which interleaves training
with inference. ``OnlineSession`` reproduces that control path on the host with
jitted device steps; all device-side state is fixed-shape.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import feedback as fb_mod
from repro.core import tm as tm_mod
from repro.core.tm import TMConfig, TMRuntime, TMState
from repro.data import buffer as buf_mod
from repro.data.memory import DataSource
from repro.kernels import packing


class SessionState(NamedTuple):
    """Device-side state of one machine — or, replica-first, of a fleet.

    The single-machine form carries the documented shapes; under
    :func:`_consume_many_replicated` (and :class:`repro.serve.fleet.
    OnlineFleet`) every leaf carries a LEADING replica axis ``[K, ...]``:
    K distinct TA banks, K ring buffers, K step counters.
    """

    tm: TMState
    buf: buf_mod.RingBuffer
    step: jax.Array  # int32 — online datapoints consumed ([K] replicated)


class ChunkAux(NamedTuple):
    """Per-chunk observability from the drain.

    One machine's shapes below (chunk size K), as ``OnlineSession``'s
    callback sees them; the drain returns every field with a LEADING
    replica axis ``[R, K]``.
    """

    predicted: jax.Array  # [K] int32 — batched inference under the post-chunk state
    correct: jax.Array    # [K] bool  — predicted == label, invalid rows False
    valid: jax.Array      # [K] bool  — rows actually consumed
    activity: jax.Array   # [K] f32   — per-step TA-update activity


def replica_gate(valid: jax.Array):
    """Per-leaf where(valid, new, old) with valid [R] broadcast over leaves.

    The replica-masked state update shared by the fleet drain and the
    fleet manager's per-replica rollback/snapshot logic."""
    def apply(a, b):
        v = valid.reshape(valid.shape + (1,) * (a.ndim - valid.ndim))
        return jnp.where(v, a, b)
    return apply


@jax.jit
def _take_rows(tree, idx):
    return jax.tree.map(lambda a: a[idx], tree)


def gather_replicas_issue(tree, idx):
    """ISSUE half of :func:`gather_replicas`: slice the named rows of
    every replica-leading leaf as DEVICE values and return immediately.

    JAX dispatch is asynchronous and arrays are immutable, so the
    returned slices stay bit-correct even after the caller functionally
    replaces the plane (activations, drain chunks) — the residency
    layer's deferred-spill path issues the gather here and materializes
    it with :func:`gather_replicas_await` only when the snapshot is
    actually read, off the inter-cohort critical path (DESIGN.md §17).
    The whole tree is sliced in ONE jitted dispatch — per-leaf eager
    gathers on a sharded plane pay ~ms of dispatch each, which at
    K=4096 dominated the cohort-move path.
    """
    return _take_rows(tree, jnp.asarray(np.asarray(idx)))


def gather_replicas_await(tree):
    """AWAIT half: materialize an issued gather to HOST numpy (blocks
    until the device slices are ready)."""
    return jax.tree.map(np.asarray, tree)


def gather_replicas(tree, idx):
    """Rows ``idx`` of every replica-leading leaf, as HOST numpy.

    The residency layer's synchronous evict path: pull the named
    device-plane slots into one stacked host tree (``[len(idx), ...]``
    per leaf) with a blocking eager gather per leaf. This is the
    ``batched_moves=False`` oracle/baseline datapath and deliberately
    stays per-leaf eager — the jitted one-dispatch slice is the batched
    path's (:func:`gather_replicas_issue`) half of the §17 win.
    """
    idx = np.asarray(idx)
    return gather_replicas_await(jax.tree.map(lambda a: a[idx], tree))


def scatter_replicas(tree, idx, values):
    """Write stacked ``values`` (leading ``len(idx)``) into rows ``idx``
    of every replica-leading leaf. The residency layer's activate path;
    dtypes are pinned to the destination leaf (int8 TA banks, uint32
    packed words and bool rows survive the host round-trip bit for bit).
    """
    idx = jnp.asarray(idx)
    return jax.tree.map(
        lambda a, v: a.at[idx].set(jnp.asarray(v, a.dtype)), tree, values
    )


@jax.jit
def activate_replicas(plane, act_plane, mask):
    """Per-slot mask-select activation: slot ``r`` takes ``act_plane``
    where ``mask[r]``, else keeps ``plane`` — ONE fused elementwise
    dispatch for a whole activation cohort (the batched-residency twin
    of :func:`scatter_replicas`, DESIGN.md §17).

    ``act_plane`` is a SLOT-INDEXED host tree (``[R, ...]`` per leaf,
    zeros in inactive rows), so there is no index scatter at all — no
    duplicate-index ordering hazard, and the select fuses with whatever
    jitted work follows in the same dispatch. Dtypes are pinned to the
    destination leaf, like the scatter path.
    """
    gate = replica_gate(mask)
    return jax.tree.map(
        lambda new, old: gate(new.astype(old.dtype), old), act_plane, plane
    )


@partial(jax.jit, static_argnums=(0, 1), static_argnames=("monitor",))
@jax.named_scope("tm.drain")   # repro.serve.obs.DRAIN; core imports no serve
def _consume_many_replicated(
    cfg: TMConfig,
    k: int,                 # static chunk size (one trace per chunk size)
    ss: SessionState,       # leaves [R, ...]
    rt: TMRuntime,          # masks shared; s/T scalar or [R]
    limit: jax.Array,       # [R] i32 — per-replica row budget for this chunk
    keys: jax.Array,        # [R] chunk keys (one RNG stream per replica)
    *,
    monitor: bool = True,   # static: False skips the monitoring pass (aux=None)
) -> tuple[SessionState, jax.Array, Optional[ChunkAux]]:
    """Drain up to ``min(k, limit[r], buffered[r])`` rows from EVERY replica
    in ONE jitted call — the fleet form of the Fig-3 online drain.

    The TA updates keep the FPGA's serial row-order semantics per replica
    (``lax.scan``: feedback at step t sees state from t-1) while each step
    advances all R machines in a single fused
    ``feedback_step_replicated`` plane (D = R: every machine owns its data
    stream). The per-datapoint inference-mode monitoring is hoisted out of
    the scan and done once per chunk as ONE replica-first batched clause
    contraction under the post-chunk states.

    Replica ``r`` is bit-identical to draining it alone (R = 1) with
    ``(ss[r], limit[r], keys[r])`` — the replicated kernels' stacking
    guarantee plus per-replica RNG streams: each chunk key splits into
    ``k`` step keys, and step ``i``'s row trains as
    ``feedback.train_update`` with step key ``i``. One machine drains as
    the R = 1 plane of this function.

    PACKED buffers (uint32 rows, DESIGN.md §13) are transparent here: each
    popped row unpacks once for the elementwise TA feedback (pack/unpack is
    exact, so the trained states are bit-identical to the unpacked path)
    while the hoisted monitoring pass consumes the packed rows directly —
    ``predict_batch_replicated_`` routes them to the AND+popcount kernels.
    """
    R = ss.step.shape[0]
    limit = jnp.asarray(limit, dtype=jnp.int32)
    packed = ss.buf.data_x.dtype == jnp.uint32          # static at trace time

    step_keys = jax.vmap(lambda kk: jax.random.split(kk, k))(keys)
    step_keys = jnp.swapaxes(step_keys, 0, 1)           # [k, R, key]

    def body(carry, inp):
        buf, tm, n = carry
        i, kk = inp                                     # scalar i32, [R] keys
        new_buf, x, y, nonempty = jax.vmap(buf_mod.pop)(buf)
        valid = (i < limit) & nonempty                  # [R]
        xb = packing.unpack_bits(x, cfg.n_features) if packed else x
        new_tm, _, activity = fb_mod.train_update_replicated(
            cfg, tm, rt, xb, y, kk
        )
        tm = jax.tree.map(replica_gate(valid), new_tm, tm)
        buf = jax.tree.map(replica_gate(valid), new_buf, buf)
        n = n + valid.astype(jnp.int32)
        return (buf, tm, n), (x, y, valid, jnp.where(valid, activity, 0.0))

    idx = jnp.arange(k, dtype=jnp.int32)
    (buf, tm, n), (xs, ys, valids, activity) = jax.lax.scan(
        body, (ss.buf, ss.tm, jnp.zeros((R,), jnp.int32)), (idx, step_keys)
    )

    # Hoisted monitoring: ONE replica-first batched inference contraction
    # over every replica's chunk. Compiled out entirely when unwanted (a
    # jitted return value can't be DCE'd).
    aux = None
    if monitor:
        preds = tm_mod.predict_batch_replicated_(
            cfg, tm, rt, jnp.swapaxes(xs, 0, 1)         # [R, k, f|Wf]
        )
        aux = ChunkAux(
            predicted=preds.astype(jnp.int32),          # [R, k]
            correct=(preds == jnp.swapaxes(ys, 0, 1)) & jnp.swapaxes(valids, 0, 1),
            valid=jnp.swapaxes(valids, 0, 1),
            activity=jnp.swapaxes(activity, 0, 1),
        )
    return SessionState(tm=tm, buf=buf, step=ss.step + n), n, aux


class OnlineSession:
    """Host-side driver for interleaved inference + online learning.

    * ``offer(x, y)``     — producer side: push into the cyclic buffer.
    * ``learn_available``  — consumer side: drain up to ``max_points`` buffered
      datapoints through online training (the per-cycle budget of Fig. 3).
    * ``infer(xs)``        — batched inference at any time.

    Since the TMService redesign this is a compatibility shim: the K = 1
    slice of :class:`repro.serve.service.TMService` (the fleet drain at
    R = 1), exposing the historical scalar-shaped ``ss``/``step``/aux
    views. Pinned bitwise to the pre-redesign implementation by
    tests/test_service.py.
    """

    def __init__(
        self,
        cfg: TMConfig,
        state: TMState,
        rt: TMRuntime,
        *,
        buffer_capacity: int = 64,
        chunk: int = 16,
        seed: int = 0,
    ):
        from repro.serve.service import ServiceConfig, TMService

        # seed as a 1-sequence: the service then consumes PRNGKey(seed)
        # exactly like the pre-redesign session (no fold_in).
        self._svc = TMService(cfg, state, ServiceConfig(
            replicas=1, buffer_capacity=buffer_capacity, chunk=chunk,
            seed=[int(seed)],
        ), rt=rt)

    @classmethod
    def _from_service(cls, svc) -> "OnlineSession":
        if svc.n_replicas != 1:
            raise ValueError("OnlineSession shims a K = 1 service only")
        sess = cls.__new__(cls)
        sess._svc = svc
        return sess

    @property
    def service(self):
        """The fleet-native surface this shim fronts (K = 1)."""
        return self._svc

    @property
    def cfg(self) -> TMConfig:
        return self._svc.cfg

    @property
    def rt(self) -> TMRuntime:
        return self._svc.rt

    @property
    def chunk(self) -> int:
        return self._svc.chunk

    @property
    def ss(self) -> SessionState:
        """The historical single-machine view: every leaf squeezed of its
        leading K = 1 replica axis."""
        return jax.tree.map(lambda a: a[0], self._svc.ss)

    @ss.setter
    def ss(self, value: SessionState):
        self._svc.ss = jax.tree.map(lambda a: jnp.asarray(a)[None], value)

    @property
    def dropped(self) -> int:
        return int(self._svc.dropped[0])  # backpressure events

    def offer(self, x, y) -> bool:
        return self._svc.submit(0, x, y)

    def fill_from(self, source: DataSource, n: int) -> int:
        """Pull ``n`` rows from a data source into the buffer."""
        accepted = 0
        for _ in range(n):
            x, y = source.next_row()
            accepted += self.offer(x, int(y))
        return accepted

    def learn_available(
        self,
        max_points: int,
        on_chunk: Optional[Callable[[ChunkAux], None]] = None,
    ) -> int:
        """Consume up to ``max_points`` buffered datapoints; returns #trained.

        Drains in chunks of ``self.chunk`` per jitted call (one device
        dispatch per chunk instead of one per datapoint); the final partial
        chunk is handled by the traced ``limit`` port, so chunk size never
        retraces.

        ``on_chunk`` (optional) receives each chunk's :class:`ChunkAux` —
        the serving-side accuracy/activity observability of the paper's
        Fig. 3 analysis block, in the historical single-machine shapes
        ([chunk], no replica axis). Without a callback the monitoring pass
        is compiled out entirely (``monitor=False``), so observability
        costs nothing unless requested.
        """
        cb = None if on_chunk is None else (
            lambda aux: on_chunk(jax.tree.map(lambda a: a[0], aux))
        )
        return int(self._svc.drain(max_points, on_chunk=cb)[0])

    def infer(self, xs) -> np.ndarray:
        return self._svc.serve(xs)[0]

    @property
    def buffered(self) -> int:
        return int(self._svc.buffered[0])
