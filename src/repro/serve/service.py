"""TMService: the one fleet-native serving surface (a single machine is K=1).

The paper's deliverable is a managed serving *system* — Fig. 3's
offer -> cyclic buffer -> interleaved train/infer loop with the §5.3.2
mitigation policy — and MATADOR (arXiv 2403.10538) plus the
runtime-tunable eFPGA TM (arXiv 2502.07823) both show the multi-instance
form winning on ONE clean control interface with per-instance
hyperparameters. :class:`TMService` is that interface here:

* ``submit`` / ``submit_rows`` — labelled traffic, staged host-side by a
  :class:`~repro.serve.router.BatchRouter` and flushed as packed
  ``[K, B_ingress]`` row-batches (one jitted dispatch per flush, not one
  per datapoint).
* ``serve`` — fleet inference, one replica-first clause contraction.
* ``tick`` — the Fig-3 consumer cycle: flush ingress, drain each
  replica's budget through online training, advance the analysis cadence
  and apply the §5.3.2 policy (:class:`AdaptPolicy`, per replica).

Everything that used to be two parallel APIs — ``OnlineSession`` /
``TMOnlineAdaptManager`` (scalar) vs ``OnlineFleet`` /
``TMFleetAdaptManager`` (``[K]``) — is now a thin shim over this class;
the K = 1 slice reproduces the scalar semantics bit for bit (pinned by
tests/test_service.py against oracles transcribed from the pre-redesign
implementations). One machine is a fleet of one: K = 1 runs the same
replicated drain, serve, analysis and offline-training bodies as every
other K, with no single-machine fork (DESIGN.md §10).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import accuracy as acc_mod
from repro.core import feedback as fb_mod
from repro.core import online as online_mod
from repro.core import tm as tm_mod
from repro.core.online import ChunkAux, SessionState
from repro.core.tm import TMConfig, TMRuntime, TMState, init_runtime
from repro.data import buffer as buf_mod
from repro.distributed import sharding as shard_mod
from repro.kernels import packing
from repro.serve import obs as obs_mod
from repro.serve import residency as res_mod
from repro.serve import router as router_mod
from repro.serve import tunable as tun_mod
from repro.train import checkpoint as ckpt_mod


@jax.jit
@jax.named_scope(obs_mod.DRAIN)
def _advance_keys(keys, active):
    """Split every ACTIVE replica's RNG key; retired replicas keep theirs.

    Returns (new persistent keys [K], chunk keys [K]). One jitted dispatch
    per chunk — a replica's key splits exactly once per chunk it
    participates in, matching a standalone session's per-chunk split (the
    chunk keys handed to retired replicas are unused: their row budget for
    the chunk is 0, so no state is touched).
    """
    k2 = jax.vmap(jax.random.split)(keys)               # [K, 2, key]
    return jnp.where(active[:, None], k2[:, 0], keys), k2[:, 1]


@partial(jax.jit, static_argnums=(2,))
@jax.named_scope(obs_mod.FLUSH)
def _activate_enqueue_rows(ss, keys, block: int, act_mask, act_ss,
                           act_keys, xs, ys, counts):
    """A residency cohort's activation select FUSED with its superblock
    enqueue — ONE device round-trip where PR 8's per-cohort path paid a
    blocking gather, an index scatter and an enqueue (DESIGN.md §17).

    ``act_ss``/``act_keys`` are the slot-indexed activation payload from
    ``TMService._prepare_slots`` (host zeros outside ``act_mask``); the
    mask-select lands the snapshots, then the staged rows push into the
    freshly activated ring buffers inside the same jitted program.
    """
    ss, keys = online_mod.activate_replicas(
        (ss, keys), (act_ss, act_keys), act_mask
    )
    ss, accepted = router_mod._enqueue_rows(ss, block, xs, ys, counts)
    return ss, keys, accepted


def _select_replicas(mask, new: TMState, old: TMState) -> TMState:
    """Per-replica tree select: replica r takes ``new`` where mask[r]."""
    gate = online_mod.replica_gate(jnp.asarray(mask))
    return jax.tree.map(gate, new, old)


# ---------------------------------------------------------------------------
# The Fig-3 FSM (§5.3.2 mitigation policy), once, on [K] arrays.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _PolicyState:
    """Host-side FSM state of :class:`AdaptPolicy`, all per replica."""

    since: np.ndarray          # [K] i64 — points consumed since last analysis
    best: np.ndarray           # [K] f64 — best known accuracy (nan = none yet)
    rollbacks: np.ndarray      # [K] i64 — §5.3.2 rollbacks fired
    lost: np.ndarray           # [K] i64 — datapoints lost even after retry
    best_state: Optional[TMState] = None   # replicated [K, ...] snapshot


@dataclasses.dataclass
class AdaptPolicy:
    """The §5.3.2 mitigation policy: periodic analysis + rollback, per replica.

    ONE implementation on ``[K]`` arrays — K = 1 yields exactly the old
    scalar ``TMOnlineAdaptManager`` semantics, K > 1 the old
    ``TMFleetAdaptManager`` semantics (both shims now delegate here; the
    ~200 duplicated FSM lines are gone). A member that consumed
    ``analyze_every`` points since its last analysis is *due*: its eval
    accuracy is re-measured, and it rolls back to its own known-good TA
    bank on a drop past ``rollback_threshold`` — or snapshots a new best.
    Members that are not due are never touched.
    """

    analyze_every: int = 32           # online datapoints between analyses
    rollback_threshold: float = 0.1   # absolute accuracy drop -> rollback

    def init(self, n_replicas: int) -> _PolicyState:
        K = n_replicas
        return _PolicyState(
            since=np.zeros(K, dtype=np.int64),
            best=np.full(K, np.nan),
            rollbacks=np.zeros(K, dtype=np.int64),
            lost=np.zeros(K, dtype=np.int64),
        )

    def due(self, ps: _PolicyState) -> np.ndarray:
        return ps.since >= self.analyze_every

    def apply(self, ps: _PolicyState, due: np.ndarray, acc: np.ndarray,
              tm: TMState) -> tuple[TMState, np.ndarray]:
        """One policy transition for the due members. Returns
        (new TA banks, rolled-back mask [K])."""
        ps.since[due] = 0
        have_best = ~np.isnan(ps.best)
        collapse = due & have_best & (acc < ps.best - self.rollback_threshold)
        improve = due & (~have_best | (acc > ps.best))
        if collapse.any():
            # §5.3.2 per replica: restore collapsed members' known-good
            # TA banks; healthy members keep serving untouched.
            tm = _select_replicas(collapse, ps.best_state, tm)
            ps.rollbacks += collapse
        if improve.any():
            ps.best = np.where(improve, acc, ps.best)
            # The very first improve is an UNCONDITIONAL snapshot:
            # ``init()`` leaves best_state None (there is no known-good
            # bank before the first analysis or offline_train), and
            # _select_replicas on a None pytree is a structure-mismatch
            # crash. Taking ``tm`` wholesale is safe for the replicas not
            # improving here: their ``best`` stays nan, so their slice of
            # the snapshot is unreachable (collapse requires have_best)
            # until their own first improve overwrites it.
            ps.best_state = (tm if ps.best_state is None
                             else _select_replicas(improve, tm, ps.best_state))
        return tm, collapse

    def snapshot(self, ps: _PolicyState, acc: np.ndarray, tm: TMState):
        """Unconditional known-good snapshot (the offline-train baseline)."""
        ps.best = np.asarray(acc, dtype=np.float64).copy()
        ps.best_state = tm


class TickReport(NamedTuple):
    """What one :meth:`TMService.tick` did, per replica."""

    trained: np.ndarray                 # [K] i64 — points consumed
    accuracy: Optional[np.ndarray]      # [K] f32 — eval accs, None if not due
    rolled_back: np.ndarray             # [K] bool — §5.3.2 rollbacks fired


@dataclasses.dataclass
class ServiceConfig:
    """Construction-time knobs of a :class:`TMService`.

    ``s``/``T`` ride the runtime's per-replica hyperparameter ports:
    scalars give a homogeneous fleet, length-K sequences give every member
    its own (s, T) without re-JIT. ``ingress_block`` is B_ingress — the
    router's staged rows per replica per flushed dispatch.

    ``packed`` switches the whole boolean datapath to the bit-packed
    uint32 representation (DESIGN.md §13): rows pack host-side at the
    router's staging boundary, the ring buffers store ceil(f/32) words
    per datapoint (~8x less ingress/buffer traffic), and every
    inference/analysis/monitoring pass runs the AND+popcount clause
    kernels. Served predictions, drained TA states and analysis
    accuracies are bit-identical to the unpacked path (which stays the
    parity oracle — pinned by tests/test_scale.py).

    ``history_limit`` bounds the analysis ``history`` list to its most
    recent N entries — a long-running service analyzing on cadence would
    otherwise grow it without bound (a memory leak at traffic scale).
    None keeps the legacy unbounded behavior.

    ``resident`` caps how many replicas hold DEVICE state at once
    (DESIGN.md §15): the device plane shrinks to ``[resident, ...]``
    slots and the other ``K - resident`` machines live as host-side LRU
    snapshots (:mod:`repro.serve.residency`), activated transparently
    when traffic, inference or analysis touches them. This is the
    thousand-replica knob — K=4096 personalization fleets on a 4-device
    mesh with bounded device memory. None (default) keeps every replica
    resident. The string ``"auto"`` (DESIGN.md §17) self-sizes the
    plane: the residency map keeps an EWMA of the per-round active-set
    size and ``tick`` re-partitions (via the checkpoint-migration
    machinery) when the estimate crosses the grow/shrink hysteresis
    bands — trajectories are unchanged across re-partitions
    (partitioning is not logical state). Requires scalar ``s``/``T``
    (a slot's runtime ports must not change meaning with the replica
    occupying it).

    ``batched_moves`` (default True) selects the batched residency
    datapath (DESIGN.md §17): activation snapshots ride the flush/drain
    dispatch as a fused mask-select and eviction gathers are issued
    asynchronously, settled off the critical path. False keeps PR 8's
    synchronous per-cohort gather/scatter sequence — bitwise identical
    (pinned by tests/test_residency.py) and the baseline
    ``benchmarks/residency.py`` measures the batched path against.

    ``tunable`` (a :class:`~repro.serve.tunable.TunableConfig`) arms the
    runtime-tunable serving path (DESIGN.md §16): after
    :meth:`TMService.calibrate` ranks every replica's clauses, ``serve``
    takes a per-call compute ``budget`` (fraction of clauses actually
    contracted), optional calibrated integer vote weights, and early-exit
    voting; with ``adapt`` on, ``tick`` moves the live budget from
    observed queue depth (load shedding under SLO pressure). Budget 1.0
    with unit weights and early exit off is bitwise identical to plain
    serving.
    """

    replicas: int = 1
    buffer_capacity: int = 64
    chunk: int = 16                   # datapoints drained per jitted call
    ingress_block: int = 32           # staged rows per replica per flush
    packed: bool = False              # bit-packed datapath (DESIGN.md §13)
    history_limit: Optional[int] = None   # analysis entries kept (None = all)
    # device slots: None = all K resident, int = fixed, "auto" = self-sizing
    resident: Union[int, None, str] = None
    batched_moves: bool = True        # batched residency datapath (§17)
    s: Union[float, Sequence[float], None] = None
    T: Union[int, Sequence[int], None] = None
    policy: AdaptPolicy = dataclasses.field(default_factory=AdaptPolicy)
    seed: Union[int, Sequence[int]] = 0
    mesh: Optional[Mesh] = None
    tunable: Optional[tun_mod.TunableConfig] = None

    def runtime(self, cfg: TMConfig) -> TMRuntime:
        """A fault-free runtime with this config's s/T ports."""
        rt = init_runtime(cfg)
        for name, port, dtype in (("s", self.s, jnp.float32),
                                  ("T", self.T, jnp.int32)):
            if port is None:
                continue
            if np.ndim(port) == 0:
                rt = rt._replace(**{name: dtype(port)})
            else:
                if len(port) != self.replicas:
                    raise ValueError(
                        f"per-replica {name} carries {len(port)} entries, "
                        f"expected {self.replicas}"
                    )
                rt = rt._replace(**{name: jnp.asarray(port, dtype)})
        return rt


class TMService:
    """K concurrent Fig-3 machines behind one control surface (K >= 1).

    Device layout is the replicated kernel contract (DESIGN.md §9/§10):
    every member owns its data stream, so state, buffers, budgets and RNG
    keys all lead with K, per-replica hyperparameters ride the runtime's
    ``s``/``T`` ports, and each drain chunk advances the whole fleet in
    ONE ``_consume_many_replicated`` call. Ingress is the
    :class:`~repro.serve.router.BatchRouter` staging queue — ``submit`` is
    a host-side numpy write; the device sees packed ``[K, B_ingress]``
    blocks.

    ``state`` may be a single machine's :class:`TMState` (broadcast to K
    identical banks) or an already-replicated ``[K, ...]`` state. ``rt``
    overrides the runtime built from ``sc.s``/``sc.T`` (shims pass their
    caller's runtime through). ``eval_x``/``eval_y`` are the accuracy-
    analysis set; without them ``tick`` still drains but never analyzes.

    Threading (DESIGN.md §14): ``submit``/``submit_rows`` are safe from
    any number of producer threads — they touch only the router's
    double-buffered staging state and the outstanding-rows mirror, both
    guarded by ``router.lock``. Everything consumer-side (device state,
    RNG keys, policy FSM, history, the runtime ``rt``) is serialized by
    one re-entrant device lock taken by ``flush``/``drain``/``tick``/
    ``serve``/``analyze``/``offline_train``; a producer only ever reaches
    the device lock through ``flush`` when its staging lane fills
    (lane-full backpressure blocks that producer until the consumer's
    current step completes). Lock order is always device -> router.
    """

    def __init__(
        self,
        cfg: TMConfig,
        state: TMState,
        sc: Optional[ServiceConfig] = None,
        *,
        rt: Optional[TMRuntime] = None,
        eval_x=None,
        eval_y=None,
    ):
        sc = sc or ServiceConfig()
        if sc.history_limit is not None and sc.history_limit < 1:
            raise ValueError("history_limit must be >= 1 (or None)")
        replicated = state.ta_state.ndim == 4
        K = sc.replicas
        if replicated and state.ta_state.shape[0] != K:
            raise ValueError(
                f"state carries {state.ta_state.shape[0]} replicas, "
                f"expected {K}"
            )
        auto = sc.resident == "auto"
        if isinstance(sc.resident, str) and not auto:
            raise ValueError(
                f"resident must be an int, None or 'auto', "
                f"got {sc.resident!r}"
            )
        if not auto and sc.resident is not None and sc.resident < 1:
            raise ValueError("resident must be >= 1 (or None, or 'auto')")
        # Auto-residency (§17): re-partition targets round up to the mesh
        # device count so the plane always shards evenly.
        granule = 1 if sc.mesh is None else int(sc.mesh.devices.size)
        if auto:
            # Start at a quarter of the fleet (granule-rounded): small
            # enough that a sparse workload shrinks within one band, big
            # enough that dense traffic grows without thrashing first.
            P = max(1, -(-K // 4))
            P = min(K, -(-P // granule) * granule)
            residency = True
        else:
            residency = sc.resident is not None and sc.resident < K
            # P: the device-plane length — R slots under residency, else K.
            P = int(sc.resident) if residency else K

        self.cfg = cfg
        self.sc = sc
        self.rt = rt if rt is not None else sc.runtime(cfg)
        self.n_replicas = K
        self.n_resident = P
        self.chunk = max(1, min(sc.chunk, sc.buffer_capacity))
        self.mesh = sc.mesh
        self.policy = sc.policy
        if cfg.patched and (residency or sc.tunable is not None):
            raise ValueError(
                "patch machines (TMConfig.window) run without residency "
                "(resident < replicas) and without the tunable serve path "
                "for now; see DESIGN.md §18"
            )
        if residency and (jnp.ndim(self.rt.s) != 0
                          or jnp.ndim(self.rt.T) != 0):
            raise ValueError(
                "residency (resident < replicas) requires scalar s/T "
                "runtime ports — a slot's hyperparameters must not "
                "change with the replica occupying it"
            )
        # Packed services hold the eval set as words too: every analysis
        # pass then rides the packed kernels (dtype routing in the core).
        self.eval_x = None if eval_x is None else self._ingest(eval_x)
        self.eval_y = None if eval_y is None else jnp.asarray(eval_y,
                                                              jnp.int32)
        seed = sc.seed
        if isinstance(seed, (int, np.integer)):
            base = jax.random.PRNGKey(int(seed))
            keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(
                jnp.arange(K)
            )
        else:
            if len(seed) != K:
                raise ValueError(f"need {K} seeds, got {len(seed)}")
            keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seed])

        buf1 = buf_mod.make(sc.buffer_capacity, cfg.n_features,
                            packed=sc.packed)
        plane_tm = (TMState(ta_state=state.ta_state[:P]) if replicated
                    else TMState(ta_state=jnp.broadcast_to(
                        state.ta_state, (P,) + state.ta_state.shape)))
        bufs = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (P,) + a.shape), buf1
        )
        self._ss = SessionState(
            tm=plane_tm, buf=bufs, step=jnp.zeros((P,), jnp.int32)
        )
        self._keys = keys if not residency else keys[:P]   # [P, key]
        if self.mesh is not None:
            sh = shard_mod.replica_shardings(
                (self._ss, self._keys), self.mesh, n_replicas=P
            )
            self._ss, self._keys = jax.tree.map(
                jax.device_put, (self._ss, self._keys), sh
            )
        # Residency (DESIGN.md §15): replicas 0..P-1 start in the device
        # slots; the rest spill as host snapshots sharing the broadcast
        # initial bank / empty buffer (snapshots are immutable in the
        # store, so sharing is safe).
        # spans and counters (repro.serve.obs); spans only while a
        # profiler trace is recording
        self.obs = obs_mod.Obs()
        self._res: Optional[res_mod.ResidencyMap] = None
        self._best_host: Optional[np.ndarray] = None  # [K, C, J, L] banks
        self._auto = auto
        self._granule = granule
        # Batched residency moves (§17): fused activate+enqueue dispatch,
        # deferred spill settlement. False = PR 8's synchronous per-cohort
        # path, kept as the bitwise oracle + bench baseline.
        self._batched = residency and sc.batched_moves
        self.repartitions = 0              # auto re-partition count
        # Deferred spills: (device value tree, rids) pairs issued but not
        # yet copied to host. Settled lazily (before any full-plane read,
        # store access, or re-activation of a pending rid) — the device
        # slices stay valid across plane replacement (JAX immutability).
        self._pending_spills: list = []
        self._pending_rids: set = set()
        if residency:
            self._res = res_mod.ResidencyMap(K, P, self.obs)
            self._res.assign(np.arange(P), np.arange(P))
            keys_host = np.asarray(keys)
            buf_host = jax.tree.map(np.asarray, buf1)
            banks_host = np.asarray(state.ta_state)
            for rid in range(P, K):
                bank = banks_host[rid] if replicated else banks_host
                self._res.store[rid] = (
                    SessionState(tm=TMState(ta_state=bank), buf=buf_host,
                                 step=np.int32(0)),
                    keys_host[rid],
                )
        self.router = router_mod.BatchRouter(
            K, cfg.n_features, sc.buffer_capacity, sc.ingress_block,
            packed=sc.packed,
        )
        # Outstanding-rows mirror: device buffer occupancy + rows in
        # flight to the device (credited at block swap, rejects undone
        # after the enqueue). Guarded by router.lock — the producer-side
        # acceptance decision reads it together with the staging counts.
        self._dev_size = np.zeros(K, dtype=np.int64)
        # Consumer-side serialization (DESIGN.md §14). Re-entrant: drain
        # flushes inside its own critical section.
        self._device_lock = threading.RLock()
        self._full_mask = np.ones(K, dtype=bool)
        # best_state starts None: there is no known-good bank before the
        # first analysis/offline_train — the policy's first improve
        # snapshots unconditionally (the old init-state pre-seed hid an
        # AdaptPolicy.apply crash on standalone-initialized policies).
        self._ps = sc.policy.init(K)
        self.history: list = []            # (steps [K], accuracies [K])
        # Runtime-tunable serving (DESIGN.md §16): the controller holds
        # per-replica clause rankings host-side — [K, ...] like _best_host,
        # so residency eviction never touches them and save/restore
        # carries them with the fleet.
        self.tuner: Optional[tun_mod.TuneController] = (
            None if sc.tunable is None
            else tun_mod.TuneController(sc.tunable, K, cfg.max_clauses)
        )

    @contextlib.contextmanager
    def _device(self):
        """The consumer-side critical section (DESIGN.md §14): the device
        lock and, on a mesh, the mesh as JAX's context mesh — the Pallas
        kernels then run per device under ``shard_map`` (kernels/ops.py)."""
        with self._device_lock, (contextlib.nullcontext() if self.mesh is None
                                 else jax.set_mesh(self.mesh)):
            yield

    def _ingest(self, xs) -> jax.Array:
        """Bool rows -> the service's wire representation: bool features
        unpacked, uint32 words when ``sc.packed`` (already-packed uint32
        input passes through)."""
        xs = jnp.asarray(xs)
        if not self.sc.packed:
            return xs.astype(bool)
        if xs.dtype == jnp.uint32:
            return xs
        return packing.pack_bits(xs.astype(bool))

    # -- device state (mirror-preserving) -----------------------------------

    @property
    def ss(self) -> SessionState:
        """Device state, with staged ingress flushed first — so externally
        read (and read-modify-written) state always contains every accepted
        datapoint, exactly like the pre-staging immediate-enqueue API.
        Under residency this is the ASSEMBLED full-K logical fleet (device
        slots gathered + spilled snapshots) — a read-only view; use
        save/restore or evict/activate to move state."""
        with self._device():
            self.flush()
            if self._res is None:
                return self._ss
            ss_K, _ = self._assemble_plane()
            return jax.tree.map(jnp.asarray, ss_K)

    @ss.setter
    def ss(self, value: SessionState):
        """Replacing device state wholesale re-syncs the occupancy mirror
        (benchmarks pre-fill buffers this way). Traffic staged but never
        read back via the getter still lands on the next flush."""
        with self._device():
            if self._res is not None:
                raise ValueError(
                    "a residency service's device plane cannot be "
                    "swapped wholesale; use restore() for bulk state"
                )
            self._ss = value
            with self.router.lock:
                self._dev_size = np.asarray(
                    value.buf.size, dtype=np.int64
                ).reshape(self.n_replicas).copy()

    def _assemble_plane(self) -> tuple[SessionState, np.ndarray]:
        """The full-K logical (SessionState, keys) as HOST numpy — device
        rows gathered into replica order, spilled snapshots filled in."""
        self._settle_spills()
        host = jax.tree.map(np.asarray, (self._ss, self._keys))
        if self._res is None:
            return host
        K = self.n_replicas
        flat_p, treedef = jax.tree_util.tree_flatten(host)
        outs = [np.zeros((K,) + l.shape[1:], l.dtype) for l in flat_p]
        m = self._res.replica_of >= 0
        rids = self._res.replica_of[m]
        for o, l in zip(outs, flat_p):
            o[rids] = l[m]
        for rid, snap in self._res.store.items():
            flat_s, _ = jax.tree_util.tree_flatten(snap)
            for o, l in zip(outs, flat_s):
                o[rid] = l
        return jax.tree_util.tree_unflatten(treedef, outs)

    # -- ingress (producer side) --------------------------------------------

    def submit_rows(self, xs, ys, mask=None) -> np.ndarray:
        """One labelled datapoint into every (masked) replica's stream;
        returns accepted [K] bool (False = backpressure, counted in
        ``dropped``). Host-side staging only — the device enqueue happens
        on the next flush (a full staging lane flushes automatically).

        Safe under concurrent producers: replicas whose lane filled while
        this call raced another producer come back *blocked* from the
        router, and the call flushes and retries them — blocked rows are
        never silently dropped nor double-staged.
        """
        pending = (self._full_mask if mask is None
                   else np.asarray(mask, dtype=bool))
        accepted = np.zeros(self.n_replicas, dtype=bool)
        while True:
            ok, blocked = self.router.stage_rows(
                xs, ys, pending, self._dev_size
            )
            accepted |= ok
            if self.router.lane_full():
                self.flush()
            if not blocked.any():
                return accepted
            pending = blocked

    def submit(self, r: int, x, y) -> bool:
        """One labelled datapoint into replica ``r``'s stream."""
        mask = np.zeros(self.n_replicas, dtype=bool)
        mask[r] = True
        return bool(self.submit_rows(x, y, mask)[r])

    def flush(self) -> np.ndarray:
        """Push every staged row to the device buffers — ONE jitted
        ``_enqueue_rows`` dispatch per staged block. Returns [K] rows
        landed. Rows a buffer rejects despite the mirror (only possible
        when device state was swapped mid-flight) count as dropped.

        The block swap and the mirror credit happen atomically under
        ``router.lock`` (taken rows are *in flight*: no longer staged,
        not yet device-visible — crediting them at swap time keeps every
        outstanding row counted exactly once by concurrent acceptance
        decisions); the device transfer itself runs outside that lock,
        overlapping producers filling the other staging block.
        """
        K = self.n_replicas
        landed = np.zeros(K, dtype=np.int64)
        with self._device(), self.obs.span(obs_mod.FLUSH):
            while True:
                with self.router.lock:
                    block = self.router.take_block()
                    if block is not None:
                        self._dev_size += block[2]
                if block is None:
                    return landed
                landed += (self._flush_block(*block) if self._res is None
                           else self._flush_block_residency(*block))

    def _flush_block(self, xs, ys, counts) -> np.ndarray:
        """One taken [K, B] staging block -> one enqueue dispatch."""
        self._ss, accepted = router_mod._enqueue_rows(
            self._ss, self.router.block, xs, ys, counts
        )
        acc = np.asarray(accepted, dtype=np.int64)
        self._count_flush(acc.sum())
        with self.router.lock:
            self._dev_size -= counts - acc
            self.router.dropped += counts - acc
        return acc

    def _flush_block_residency(self, xs, ys, counts) -> np.ndarray:
        """One taken [K, B] block under residency: the full hot-lane set
        is built host-side ONCE per round, then lands cohort by cohort
        through :meth:`_enqueue_lanes` — the batched path (§17) fuses
        each cohort's activation select with its superblock enqueue into
        one dispatch; ``batched_moves=False`` keeps PR 8's synchronous
        per-cohort gather/scatter/enqueue sequence as the oracle."""
        lanes = np.nonzero(np.asarray(counts) > 0)[0]
        return self._enqueue_lanes(lanes, xs[lanes], ys[lanes],
                                   counts[lanes])

    def _enqueue_lanes(self, lanes, xs_l, ys_l, cnt_l) -> np.ndarray:
        """Land the given lanes' staged rows (lane-indexed [n, B, ...])
        into their replicas' device rings, cohorting by the slot count.
        Returns [K] rows landed (mirror + drop accounting per cohort)."""
        K, R = self.n_replicas, self.n_resident
        landed = np.zeros(K, dtype=np.int64)
        for i in range(0, len(lanes), R):
            sl = slice(i, i + R)
            cohort = lanes[sl]
            enqueue = (self._enqueue_cohort_batched if self._batched
                       else self._enqueue_cohort_sync)
            acc = enqueue(cohort, xs_l[sl], ys_l[sl], cnt_l[sl])
            rej = np.asarray(cnt_l[sl], dtype=np.int64) - acc
            with self.router.lock:
                self._dev_size[cohort] -= rej
                self.router.dropped[cohort] += rej
            landed[cohort] += acc
        return landed

    def _enqueue_cohort_sync(self, cohort, xs_c, ys_c,
                             cnt_c) -> np.ndarray:
        """PR 8's per-cohort path: synchronous activation (blocking
        gather + index scatter), then a separate enqueue dispatch. The
        bitwise oracle the batched path is pinned against
        (tests/test_residency.py) and the baseline it is benched
        against (benchmarks/residency.py)."""
        R = self.n_resident
        slots = self._ensure_resident(cohort)
        xs_p = np.zeros((R,) + xs_c.shape[1:], dtype=xs_c.dtype)
        ys_p = np.zeros((R,) + ys_c.shape[1:], dtype=ys_c.dtype)
        cnt_p = np.zeros((R,), dtype=cnt_c.dtype)
        xs_p[slots] = xs_c
        ys_p[slots] = ys_c
        cnt_p[slots] = cnt_c
        self._ss, accepted = router_mod._enqueue_rows(
            self._ss, self.router.block, xs_p, ys_p, cnt_p
        )
        acc = np.asarray(accepted, dtype=np.int64)[slots]
        self._count_flush(acc.sum())
        return acc

    def _enqueue_cohort_batched(self, cohort, xs_c, ys_c,
                                cnt_c) -> np.ndarray:
        """§17 batched cohort: prepare the slots (victim gathers ISSUED,
        not awaited; activation snapshots stacked into slot-indexed host
        planes), scatter the lane rows to the [R, B] superblock, then
        ONE fused activate+enqueue dispatch. Pending spill copies settle
        only after the dispatch is in flight, so the device->host
        drain of cohort i's victims overlaps cohort i+1's device work."""
        R = self.n_resident
        slots, act = self._prepare_slots(cohort)
        xs_p = np.zeros((R,) + xs_c.shape[1:], dtype=xs_c.dtype)
        ys_p = np.zeros((R,) + ys_c.shape[1:], dtype=ys_c.dtype)
        cnt_p = np.zeros((R,), dtype=cnt_c.dtype)
        xs_p[slots] = xs_c
        ys_p[slots] = ys_c
        cnt_p[slots] = cnt_c
        if act is None:
            self._ss, accepted = router_mod._enqueue_rows(
                self._ss, self.router.block, xs_p, ys_p, cnt_p
            )
        else:
            act_mask, (act_ss, act_keys) = act
            self._ss, self._keys, accepted = _activate_enqueue_rows(
                self._ss, self._keys, self.router.block,
                act_mask, act_ss, act_keys, xs_p, ys_p, cnt_p,
            )
            self._reshard_plane()
        self._settle_spills()
        acc = np.asarray(accepted, dtype=np.int64)[slots]
        self._count_flush(acc.sum())
        return acc

    def _count_flush(self, rows) -> None:
        """One ``_enqueue_rows`` dispatch: the rows it landed and the
        staging slots it carried (every lane of the plane, whatever is
        staged)."""
        self.obs.add("flush.rows", rows)
        self.obs.add("flush.slots", self.n_resident * self.router.block)

    def _reshard_plane(self) -> None:
        """Re-pin the device plane's sharding after a dispatch whose
        host-side activation operands carried no placement (mesh only;
        a no-op move when the compiler already kept the layout)."""
        if self.mesh is None:
            return
        plane = (self._ss, self._keys)
        sh = shard_mod.replica_shardings(
            plane, self.mesh, n_replicas=self.n_resident
        )
        self._ss, self._keys = jax.tree.map(jax.device_put, plane, sh)

    # -- residency (DESIGN.md §15) ------------------------------------------

    @property
    def resident(self) -> np.ndarray:
        """[K] bool — replicas holding device state right now (all True
        on a service without a residency layer)."""
        if self._res is None:
            return np.ones(self.n_replicas, dtype=bool)
        return self._res.resident_mask.copy()

    def _ensure_resident(self, rids) -> np.ndarray:
        """Device slots for the named replicas, activating evicted ones
        (spilling LRU residents to make room). Callers hold the device
        lock; a cohort is at most ``n_resident`` distinct replicas."""
        if not self._batched:
            return self._ensure_resident_sync(rids)
        slots, act = self._prepare_slots(rids)
        if act is not None:
            act_mask, act_plane = act
            self._ss, self._keys = online_mod.activate_replicas(
                (self._ss, self._keys), act_plane, act_mask
            )
            self._reshard_plane()
        return slots

    def _ensure_resident_sync(self, rids) -> np.ndarray:
        """PR 8's synchronous residency body (``batched_moves=False``):
        blocking gather on spill, index scatter on activate."""
        res = self._res
        rids = np.asarray(rids, dtype=np.int64).reshape(-1)
        if len(rids) > self.n_resident:
            raise ValueError(
                f"cohort of {len(rids)} replicas exceeds the "
                f"{self.n_resident} device slots"
            )
        if len(np.unique(rids)) != len(rids):
            raise ValueError("duplicate replicas in a residency cohort")
        need = rids[res.slot_of[rids] < 0]
        if len(need):
            free = res.free_slots()
            take = list(free[:len(need)])
            short = len(need) - len(take)
            if short > 0:
                pinned = res.slot_of[rids]
                victims = res.lru_victims(short, pinned[pinned >= 0])
                self._spill(victims)
                take += list(victims)
            self._activate(need, np.asarray(take[:len(need)],
                                            dtype=np.int64))
        slots = res.slot_of[rids]
        res.touch(slots)
        return slots

    def _prepare_slots(self, rids):
        """Slots for the named cohort, with the activation BUILT but not
        landed: victims' device gathers are issued (not awaited) and the
        evicted members' snapshots stack into slot-indexed [R, ...] host
        planes plus an activation mask — ready to ride a fused dispatch
        (§17). Returns (slots [n], None | (act_mask [R],
        (act_ss_plane, act_keys_plane)))."""
        res = self._res
        R = self.n_resident
        rids = np.asarray(rids, dtype=np.int64).reshape(-1)
        if len(rids) > R:
            raise ValueError(
                f"cohort of {len(rids)} replicas exceeds the "
                f"{R} device slots"
            )
        if len(np.unique(rids)) != len(rids):
            raise ValueError("duplicate replicas in a residency cohort")
        need = rids[res.slot_of[rids] < 0]
        if len(need) == 0:
            slots = res.slot_of[rids]
            res.touch(slots)
            return slots, None
        free = res.free_slots()
        take = list(free[:len(need)])
        short = len(need) - len(take)
        if short > 0:
            pinned = res.slot_of[rids]
            victims = res.lru_victims(short, pinned[pinned >= 0])
            self._spill_issue(victims)
            take += list(victims)
        take = np.asarray(take[:len(need)], dtype=np.int64)
        # Re-activating a replica whose spill is still in flight needs
        # the snapshot NOW — its bits exist only in the deferred device
        # slices until a settle writes the store.
        if self._pending_rids.intersection(int(r) for r in need):
            self._settle_spills()
        snaps = [res.store.pop(int(r)) for r in need]
        vals = jax.tree.map(lambda *xs: np.stack(xs), *snaps)

        def to_plane(leaf):
            leaf = np.asarray(leaf)
            out = np.zeros((R,) + leaf.shape[1:], dtype=leaf.dtype)
            out[take] = leaf
            return out

        act_plane = jax.tree.map(to_plane, vals)
        act_mask = np.zeros(R, dtype=bool)
        act_mask[take] = True
        res.assign(need, take)
        slots = res.slot_of[rids]
        res.touch(slots)
        return slots, (act_mask, act_plane)

    def _spill_issue(self, slots) -> None:
        """ISSUE the device->host gather for the replicas in the given
        slots without awaiting it: the sliced device values (immutable,
        so bit-correct across later plane replacements) park on the
        pending list and materialize at the next settle point — off the
        inter-cohort critical path (§17)."""
        slots = np.asarray(slots, dtype=np.int64)
        if len(slots) == 0:
            return
        vals = online_mod.gather_replicas_issue(
            (self._ss, self._keys), slots
        )
        rids = self._res.release(slots)
        self._pending_spills.append((vals, rids))
        self._pending_rids.update(int(r) for r in rids)

    def _settle_spills(self) -> None:
        """Materialize every pending spill into the host store. Cheap
        no-op when nothing is pending; every full-plane read
        (_assemble_plane, steps, bank access) settles first."""
        if not self._pending_spills:
            return
        pending, self._pending_spills = self._pending_spills, []
        self._pending_rids.clear()
        for vals, rids in pending:
            host = online_mod.gather_replicas_await(vals)
            for j, rid in enumerate(rids):
                self._res.store[int(rid)] = jax.tree.map(
                    lambda a, _j=j: a[_j], host
                )

    def _spill(self, slots) -> None:
        """Evict the replicas in the given slots: one device->host gather,
        complete per-machine snapshots into the LRU store."""
        slots = np.asarray(slots, dtype=np.int64)
        if len(slots) == 0:
            return
        vals = online_mod.gather_replicas((self._ss, self._keys), slots)
        rids = self._res.release(slots)
        for j, rid in enumerate(rids):
            self._res.store[int(rid)] = jax.tree.map(lambda a: a[j], vals)

    def _activate(self, rids, slots) -> None:
        """Load the named (evicted) replicas' snapshots into free slots:
        one host->device scatter per cohort."""
        snaps = [self._res.store.pop(int(r)) for r in rids]
        vals = jax.tree.map(lambda *xs: np.stack(xs), *snaps)
        plane = online_mod.scatter_replicas(
            (self._ss, self._keys), slots, vals
        )
        if self.mesh is not None:
            sh = shard_mod.replica_shardings(
                plane, self.mesh, n_replicas=self.n_resident
            )
            plane = jax.tree.map(jax.device_put, plane, sh)
        self._ss, self._keys = plane
        self._res.assign(np.asarray(rids, dtype=np.int64), slots)

    def evict(self, replicas) -> None:
        """Spill the named replicas to the host store. Their staged
        ingress lands first — scoped to THEIR lanes only via
        :meth:`BatchRouter.take_lanes` (a K=4096 fleet must not pay a
        whole-fleet flush to spill a handful of members; other lanes'
        staged rows stay staged). Any later submit/serve/analysis
        touching the evicted members re-activates transparently."""
        with self._device():
            if self._res is None:
                raise ValueError(
                    "service has no residency layer (resident is None)"
                )
            rids = np.unique(
                np.asarray(replicas, dtype=np.int64).reshape(-1)
            )
            with self.router.lock:
                taken = self.router.take_lanes(rids)
                if taken is not None:
                    # taken rows are in flight: credit the mirror at the
                    # take, debit rejects after the enqueue — same
                    # accounting as the block-swap flush
                    self._dev_size[rids] += taken[2]
            if taken is not None:
                xs_l, ys_l, cnt_l = taken
                hot = np.nonzero(cnt_l > 0)[0]
                self._enqueue_lanes(rids[hot], xs_l[hot], ys_l[hot],
                                    cnt_l[hot])
            slots = self._res.slot_of[rids]
            slots = np.unique(slots[slots >= 0])
            if self._batched:
                # an explicit evict wants the snapshots durable NOW (the
                # caller may read svc.ss or save() without another op)
                self._spill_issue(slots)
                self._settle_spills()
            else:
                self._spill(slots)

    def activate(self, replicas) -> np.ndarray:
        """Make the named replicas device-resident (at most ``resident``
        of them); returns their slots."""
        with self._device():
            if self._res is None:
                raise ValueError(
                    "service has no residency layer (resident is None)"
                )
            return self._ensure_resident(replicas)

    @property
    def buffered(self) -> np.ndarray:
        """Datapoints awaiting consumption per replica (device + in-flight
        + staged; read coherently under the router lock)."""
        with self.router.lock:
            return self._dev_size + self.router.staged

    @property
    def dropped(self) -> np.ndarray:
        """Backpressure events per replica. [K] i64 (a copy)."""
        with self.router.lock:
            return self.router.dropped.copy()

    # -- consumer side ------------------------------------------------------

    def drain(
        self,
        max_points,
        on_chunk: Optional[Callable[[ChunkAux], None]] = None,
    ) -> np.ndarray:
        """Consume up to ``max_points`` buffered rows PER REPLICA; [K]
        trained. Flushes staged ingress first, then drains chunk by chunk
        — one jitted call per chunk for the whole fleet (the per-cycle
        budget of Fig. 3, K machines per dispatch). Per-replica
        RNG/termination semantics exactly mirror K independent sessions.

        ``on_chunk`` receives each chunk's :class:`ChunkAux` with leading
        replica axis ``[K, chunk]``; without it the monitoring contraction
        is compiled out entirely.
        """
        K = self.n_replicas
        budget = np.broadcast_to(
            np.asarray(max_points, dtype=np.int64), (K,)
        ).copy()
        # the drain bodies keep the occupancy mirror in sync per chunk (not
        # here, after the fact) so an on_chunk callback raising mid-drain
        # can't desync accounting from the device
        with self._device():
            self.flush()
            with self.obs.span(obs_mod.DRAIN):
                return self._drain_locked(budget, on_chunk)

    def _drain_locked(self, budget, on_chunk) -> np.ndarray:
        """:meth:`drain`'s chunk loop, after the flush."""
        K = self.n_replicas
        if self._res is None:
            return self._drain_replicated(budget, on_chunk)
        # Residency: sweep EVERY replica holding buffered rows (and
        # budget) in cohorts of <= resident slots — no lane starves
        # behind the working set, and sparse traffic only ever
        # activates its own users. A replica with budget but no
        # buffered rows is skipped entirely, so its RNG key does not
        # split; the always-resident twin property therefore masks
        # budgets by ``buffered > 0`` (tests/test_residency.py).
        trained = np.zeros(K, dtype=np.int64)
        with self.router.lock:
            has_rows = self._dev_size > 0
        todo = np.nonzero(has_rows & (budget > 0))[0]
        # the active-set size is the autotune signal (§17): how many
        # replicas actually need a slot this round
        self._res.note_active(len(todo))
        R = self.n_resident
        for i in range(0, len(todo), R):
            cohort = todo[i:i + R]
            slots = self._ensure_resident(cohort)
            budget_p = np.zeros(R, dtype=np.int64)
            budget_p[slots] = budget[cohort]
            trained_p = self._drain_replicated(budget_p, on_chunk)
            trained[cohort] = trained_p[slots]
        self._settle_spills()
        return trained

    def _drain_replicated(self, budget, on_chunk) -> np.ndarray:
        K = len(budget)   # the device-plane length (= slots, not fleet K)
        trained = np.zeros(K, dtype=np.int64)
        active = trained < budget
        monitor = on_chunk is not None
        while active.any():
            want = np.where(
                active, np.minimum(self.chunk, budget - trained), 0
            ).astype(np.int32)
            self._keys, chunk_keys = _advance_keys(
                self._keys, jnp.asarray(active)
            )
            self._ss, n, aux = online_mod._consume_many_replicated(
                self.cfg, self.chunk, self._ss, self.rt,
                jnp.asarray(want), chunk_keys, monitor=monitor,
            )
            self._count_patch_evals("train", K * self.chunk)
            if monitor:
                self._count_patch_evals("monitor", K * self.chunk)
            n = np.asarray(n, dtype=np.int64)
            trained += n
            with self.router.lock:
                self._debit_mirror(n)
            if monitor and n.any():
                on_chunk(aux)
            active &= (n == want) & (trained < budget)
        return trained

    def _count_patch_evals(self, pass_: str, row_slots: int) -> None:
        """``patch.evals.<pass>``: row slots x patches put through patch
        evaluation by a dispatch (patch machines only)."""
        if self.cfg.patched:
            self.obs.add(f"{obs_mod.PATCH_EVALS}.{pass_}",
                         row_slots * self.cfg.n_patches)

    def _debit_mirror(self, n_plane) -> None:
        """Map a device-plane consumed-rows vector onto the [K] mirror
        (identity without residency). Callers hold the router lock."""
        if self._res is None:
            self._dev_size -= n_plane
        else:
            m = self._res.replica_of >= 0
            np.subtract.at(self._dev_size, self._res.replica_of[m],
                           n_plane[m])

    # -- inference ----------------------------------------------------------

    def serve(self, xs, *, budget=None, return_aux: bool = False):
        """Fleet inference [K, B]: every member's batch in ONE contraction.

        ``xs`` is [B, f] (the same batch served by all members) or
        [K, B, f] (one batch per member). Packed services pack the batch
        here and serve it through the AND+popcount kernels, bit-identically.

        ``budget`` (fraction of clauses, (0, 1]) routes the request
        through the runtime-tunable path (DESIGN.md §16): only the top-m
        ranked clauses per class are contracted, with the configured
        weights/early-exit applied. Requires ``ServiceConfig(tunable=...)``
        and a prior :meth:`calibrate`. Without an explicit budget, a
        tunable service serves at the controller's live budget (plain
        path when that is 1.0 with unit weights and no early exit).
        ``return_aux`` additionally returns the
        :class:`~repro.serve.tunable.ServeAux` (elected clause ids +
        per-request evaluated counts) — tunable path only.

        A residency service cannot serve the whole fleet in one
        contraction (only ``resident`` machines are on device) — use
        :meth:`serve_replicas` to name the members a request targets.
        """
        xs = self._ingest(xs)
        with self._device():
            if self._res is not None:
                raise ValueError(
                    "TMService.serve needs the whole fleet device-resident, "
                    f"but ServiceConfig(resident={self.sc.resident}) < "
                    f"replicas={self.n_replicas} spills part of it: use "
                    "serve_replicas(replicas, xs) to serve named members "
                    "(activated on demand), or raise the 'resident' knob to "
                    "cover the fleet"
                )
            tunable = budget is not None or (
                self.tuner is not None and self.tuner.active
            )
            if not tunable:
                if return_aux:
                    raise ValueError(
                        "return_aux reports the budgeted path's compute — "
                        "pass a budget (or configure an active tunable)"
                    )
                if xs.ndim == 2:
                    # D = 1: one shared stream, factored (stored once)
                    xs = xs[None]
                return np.asarray(tm_mod.predict_batch_replicated(
                    self.cfg, self._ss.tm, self.rt, xs
                ))
            tuner = self._require_tuner()
            preds, aux = self._serve_tunable(
                self._ss.tm, xs, tuner.order, tuner.weights, budget
            )
            return (preds, aux) if return_aux else preds

    def _require_tuner(self) -> tun_mod.TuneController:
        if self.tuner is None:
            raise ValueError(
                "budgeted serving needs ServiceConfig(tunable=TunableConfig"
                "(...)) — this service was built without it"
            )
        if not self.tuner.calibrated:
            raise ValueError(
                "budgeted serving needs clause ranks: call calibrate() "
                "(after training) before serving with a budget"
            )
        return self.tuner

    def _serve_tunable(
        self, tm_plane, xs, order, weights, budget
    ) -> tuple[np.ndarray, tun_mod.ServeAux]:
        """The budgeted serve body on an already-gathered device plane.
        ``order``/``weights`` rows must align with the plane's rows."""
        tc = self.sc.tunable
        b = self.tuner.budget if budget is None else float(budget)
        m = tun_mod.m_for_budget(b, self.cfg.max_clauses)
        if xs.ndim == 2:
            xs = xs[None]     # D = 1: one shared stream
        preds, evaluated = tun_mod.predict_pruned_replicated_host(
            self.cfg, tm_plane, self.rt, xs, order, weights, m,
            group=tc.group if tc.early_exit else None,
        )
        aux = tun_mod.ServeAux(
            budget=b, m=m, sel=order[:, :, :m].copy(), evaluated=evaluated
        )
        return preds, aux

    def serve_replicas(self, replicas, xs, *, budget=None,
                       return_aux: bool = False):
        """Inference for the NAMED replicas only: [n, B] predictions.

        ``xs`` is [B, f] (one batch shared by the named members) or
        [n, B, f] (one per member). Under residency, evicted members are
        activated in cohorts of at most ``resident`` (LRU-spilling as
        needed), so a K=4096 fleet serves any subset on bounded device
        memory; predictions are bit-identical to an always-resident
        fleet's (prediction never touches the s/T ports, so the gathered
        sub-plane contraction is exact).

        ``budget``/``return_aux`` as in :meth:`serve` — each named member
        serves from its OWN calibrated ranking (rankings are host-side
        per-replica state, so they survive eviction; the cohort gather
        reads them by replica id, not by slot).
        """
        xs = self._ingest(xs)
        rids = np.asarray(replicas, dtype=np.int64).reshape(-1)
        shared = xs.ndim == 2
        cap = self.n_resident
        tunable = budget is not None or (
            self.tuner is not None and self.tuner.active
        )
        if return_aux and not tunable:
            raise ValueError(
                "return_aux reports the budgeted path's compute — pass a "
                "budget (or configure an active tunable)"
            )
        tuner = self._require_tuner() if tunable else None
        outs, auxes = [], []
        with self._device():
            for i in range(0, len(rids), cap):
                cohort = rids[i:i + cap]
                slots = (cohort if self._res is None
                         else self._ensure_resident(cohort))
                tm_c = jax.tree.map(lambda a: a[jnp.asarray(slots)],
                                    self._ss.tm)
                xs_c = xs[None] if shared else xs[i:i + cap]
                if not tunable:
                    outs.append(np.asarray(tm_mod.predict_batch_replicated(
                        self.cfg, tm_c, self.rt, xs_c
                    )))
                    continue
                w_c = (None if tuner.weights is None
                       else tuner.weights[cohort])
                preds, aux = self._serve_tunable(
                    tm_c, xs_c, tuner.order[cohort], w_c, budget
                )
                outs.append(preds)
                auxes.append(aux)
        preds = np.concatenate(outs, axis=0)
        if not return_aux:
            return preds
        aux = tun_mod.ServeAux(
            budget=auxes[0].budget, m=auxes[0].m,
            sel=np.concatenate([a.sel for a in auxes], axis=0),
            evaluated=np.concatenate([a.evaluated for a in auxes], axis=0),
        )
        return preds, aux

    def calibrate(self, xs=None, ys=None) -> np.ndarray:
        """Rank every replica's clauses from a calibration set (default:
        the eval set); derives integer vote weights when the tunable
        config asks for them. Returns the [K, C, J] score plane.

        Under residency the fleet calibrates in cohorts of at most
        ``resident`` slots (evicted members activate transparently, like
        the analysis sweep) — ranks land host-side per replica either
        way. Recalibrate whenever the banks have drifted enough that the
        ranking should follow (e.g. after offline_train or a long online
        phase); serving between calibrations just uses the older ranks.
        """
        if self.tuner is None:
            raise ValueError(
                "calibrate needs ServiceConfig(tunable=TunableConfig(...))"
            )
        xs = self.eval_x if xs is None else self._ingest(xs)
        ys = self.eval_y if ys is None else jnp.asarray(ys, jnp.int32)
        if xs is None or ys is None:
            raise ValueError(
                "calibrate needs a labelled set: pass (xs, ys) or build "
                "the service with eval_x/eval_y"
            )
        K = self.n_replicas
        C, J = self.cfg.max_classes, self.cfg.max_clauses
        scores = np.zeros((K, C, J), dtype=np.int32)
        with self._device():
            if self._res is None:
                scores[:] = np.asarray(tun_mod.clause_scores_replicated(
                    self.cfg, self._ss.tm, self.rt, xs[None], ys[None]
                ))
            else:
                for i in range(0, K, self.n_resident):
                    cohort = np.arange(i, min(i + self.n_resident, K))
                    slots = self._ensure_resident(cohort)
                    tm_c = jax.tree.map(lambda a: a[jnp.asarray(slots)],
                                        self._ss.tm)
                    scores[cohort] = np.asarray(
                        tun_mod.clause_scores_replicated(
                            self.cfg, tm_c, self.rt, xs[None], ys[None]
                        ))
            self.tuner.set_ranking(
                tun_mod.rank_from_scores(
                    scores, np.asarray(tm_mod.clause_polarity(self.cfg))
                ),
                tun_mod.weights_from_scores(
                    scores, self.sc.tunable.weight_bits
                ),
                score=scores,
            )
        return scores

    # -- analysis + the Fig-3 policy loop -----------------------------------

    def analyze(self) -> np.ndarray:
        """Eval accuracy of every member in ONE contraction. [K] f32.

        Under residency only the device-resident members measure; evicted
        members read nan (``activate`` them first for a full sweep — the
        policy loop does exactly that for its due members)."""
        if self.eval_x is None:
            raise ValueError("TMService built without an eval set")
        with self._device():
            acc = self._measure()
            self.history.append((self.steps, acc))
            if self.sc.history_limit is not None:
                del self.history[:-self.sc.history_limit]
            return acc

    def _measure(self) -> np.ndarray:
        """One eval contraction over the device plane; [K] f32 (nan for
        evicted replicas). No history side effects."""
        acc_p = np.asarray(acc_mod.analyze_replicated(
            self.cfg, self._ss.tm, self.rt,
            self.eval_x[None], self.eval_y[None],  # D = 1: shared
        ))
        self._count_patch_evals("analysis",
                                len(acc_p) * self.eval_x.shape[0])
        if self._res is None:
            return acc_p
        acc = np.full(self.n_replicas, np.nan, dtype=np.float32)
        m = self._res.replica_of >= 0
        acc[self._res.replica_of[m]] = acc_p[m]
        return acc

    def offline_train(self, xs, ys, n_epochs: int = 10,
                      seed: int = 1) -> np.ndarray:
        """Offline phase for the whole fleet (one replicated epochs scan);
        the result becomes every member's known-good baseline."""
        xs = jnp.asarray(xs, dtype=bool)
        ys = jnp.asarray(ys, dtype=jnp.int32)
        with self._device():
            if self._res is not None:
                raise ValueError(
                    "offline_train needs the full fleet device-resident; "
                    "train a full-resident service (or a single machine) "
                    "first, then construct the residency service from its "
                    "state"
                )
            return self._offline_train_locked(xs, ys, n_epochs, seed)

    def _offline_train_locked(self, xs, ys, n_epochs, seed) -> np.ndarray:
        st = fb_mod.train_epochs_replicated(
            self.cfg, self._ss.tm, self.rt, xs[None], ys[None],
            jax.random.PRNGKey(seed)[None], n_epochs,
        )
        self._ss = self._ss._replace(tm=st)
        acc = self.analyze()
        self.policy.snapshot(self._ps, acc, st)
        return acc

    def _maybe_analyze(self) -> Optional[tuple[np.ndarray, np.ndarray]]:
        """Run analysis + the §5.3.2 policy if any member is due.
        Returns (accuracies [K], rolled-back mask [K]) or None."""
        if self.eval_x is None:
            return None
        due = self.policy.due(self._ps)
        if not due.any():
            return None
        if self._res is not None:
            return self._analyze_residency(due)
        with self.obs.span(obs_mod.ANALYSIS):
            acc = self.analyze()
        with self.obs.span(obs_mod.POLICY):
            tm, rolled = self.policy.apply(self._ps, due, acc, self._ss.tm)
        self._ss = self._ss._replace(tm=tm)
        return acc, rolled

    def _analyze_residency(self, due) -> tuple[np.ndarray, np.ndarray]:
        """The §5.3.2 transition under residency: measure the due members
        (activating evicted ones cohort by cohort), append ONE history
        entry, then run the policy FSM with the known-good banks living
        host-side (one [K, ...] numpy array — ``_best_host`` — instead of
        a device-resident snapshot tree)."""
        with self.obs.span(obs_mod.ANALYSIS):
            acc = self._measure()
            missing = due & np.isnan(acc)
            while missing.any():
                ids = np.nonzero(missing)[0][: self.n_resident]
                self._ensure_resident(ids)
                fresh = self._measure()
                acc = np.where(np.isnan(acc), fresh, acc).astype(np.float32)
                missing = due & np.isnan(acc)
        self.history.append((self.steps, acc))
        if self.sc.history_limit is not None:
            del self.history[:-self.sc.history_limit]
        with self.obs.span(obs_mod.POLICY):
            rolled = self._policy_apply_residency(due, acc)
        return acc, rolled

    def _policy_apply_residency(self, due, acc) -> np.ndarray:
        """AdaptPolicy.apply's FSM on host-side known-good banks. The
        transition rules are identical (same since/best/collapse/improve
        algebra on the [K] arrays); only the snapshot storage differs —
        scatters into ``_best_host`` on improve, per-replica bank writes
        (device slot or spilled snapshot) on collapse."""
        ps, pol = self._ps, self.policy
        ps.since[due] = 0
        measured = due & ~np.isnan(acc)
        have_best = ~np.isnan(ps.best)
        collapse = measured & have_best & (
            acc < ps.best - pol.rollback_threshold)
        improve = measured & (~have_best | (acc > ps.best))
        if collapse.any():
            for rid in np.nonzero(collapse)[0]:
                self._write_bank(int(rid), self._best_host[rid])
            ps.rollbacks += collapse
        if improve.any():
            if self._best_host is None:
                ta = self._ss.tm.ta_state
                self._best_host = np.zeros(
                    (self.n_replicas,) + tuple(ta.shape[1:]),
                    dtype=np.dtype(ta.dtype),
                )
            for rid in np.nonzero(improve)[0]:
                self._best_host[rid] = self._read_bank(int(rid))
            ps.best = np.where(improve, acc, ps.best)
        return collapse

    def _read_bank(self, rid: int) -> np.ndarray:
        self._settle_spills()
        slot = int(self._res.slot_of[rid])
        if slot >= 0:
            return np.asarray(self._ss.tm.ta_state[slot])
        return np.asarray(self._res.store[rid][0].tm.ta_state)

    def _write_bank(self, rid: int, bank) -> None:
        self._settle_spills()
        slot = int(self._res.slot_of[rid])
        if slot >= 0:
            ta = self._ss.tm.ta_state
            self._ss = self._ss._replace(tm=TMState(
                ta_state=ta.at[slot].set(jnp.asarray(bank, ta.dtype))
            ))
        else:
            ss_s, key_s = self._res.store[rid]
            self._res.store[rid] = (
                ss_s._replace(tm=TMState(ta_state=np.array(bank))),
                key_s,
            )

    def tick(
        self,
        max_points=None,
        on_chunk: Optional[Callable[[ChunkAux], None]] = None,
    ) -> TickReport:
        """One Fig-3 consumer cycle: flush ingress, drain up to
        ``max_points`` (default: one chunk) per replica, advance the
        analysis cadence, and apply the mitigation policy to due members.
        """
        budget = self.chunk if max_points is None else max_points
        with self._device():
            trained = self.drain(budget, on_chunk)
            self._ps.since += trained
            if self._auto:
                target = self._res.autotune_target(granule=self._granule)
                if target != self.n_resident:
                    self._repartition(target)
            out = self._maybe_analyze()
            if self.tuner is not None and self.sc.tunable.adapt:
                # SLO pressure valve (§16): post-drain queue depth is the
                # observed backlog — deep queues shed serve compute, light
                # queues restore it (never above the configured budget).
                self.tuner.update(self.buffered)
        if out is None:
            return TickReport(trained, None,
                              np.zeros(self.n_replicas, dtype=bool))
        return TickReport(trained, out[0], out[1])

    def observe_rows(self, xs, ys, mask=None) -> Optional[np.ndarray]:
        """The legacy managers' per-point FSM step: one labelled datapoint
        per (masked) replica, drain-retry backpressure, one chunk-budget
        drain, then cadence/analysis/rollback. Returns [K] eval accuracies
        when at least one member hit its cadence, None otherwise.

        Drained points advance each member's OWN cadence counter — a
        backpressure drain's points still count toward the analysis
        cadence, exactly like the pre-redesign managers.
        """
        K = self.n_replicas
        mask = (np.ones(K, dtype=bool) if mask is None
                else np.asarray(mask, dtype=bool))
        with self._device():
            accepted = self.submit_rows(xs, ys, mask)
            retry = mask & ~accepted
            if retry.any():
                # Backpressure: drain a chunk fleet-wide, then retry once.
                self._ps.since += self.drain(self.chunk)
                accepted = self.submit_rows(xs, ys, retry)
                self._ps.lost += retry & ~accepted
            self._ps.since += self.drain(self.chunk)
            out = self._maybe_analyze()
        return None if out is None else out[0]

    # -- durable state (save / restore; DESIGN.md §15) ----------------------

    def save(self, directory: str, *, step: Optional[int] = None,
             keep: int = 3) -> str:
        """Write the FULL consumer-side state as one atomic checkpoint
        (train/checkpoint.py layout): TA banks, ring buffers, step
        counters, RNG keys, the §5.3.2 policy FSM including the
        known-good banks, the analysis history and the router's loss
        counters. Staged ingress flushes first, so every accepted
        datapoint is either in a saved ring buffer or already consumed —
        save -> restore -> continue is bitwise identical to never
        stopping. Residency services save the ASSEMBLED full-K logical
        fleet: the checkpoint is residency-agnostic and restores under
        any ``resident`` budget (migration across device budgets).
        Returns the checkpoint path."""
        self._refuse_patched_checkpoint()
        with self._device():
            self.flush()
            ss_K, keys_K = self._assemble_plane()
            ps = self._ps
            if self._res is not None:
                best = (None if self._best_host is None
                        else TMState(ta_state=self._best_host))
            else:
                best = ps.best_state
            if self.history:
                hsteps = np.stack([np.asarray(h[0]) for h in self.history])
                haccs = np.stack([np.asarray(h[1]) for h in self.history])
            else:
                hsteps = np.zeros((0, self.n_replicas), dtype=np.int32)
                haccs = np.zeros((0, self.n_replicas), dtype=np.float32)
            with self.router.lock:
                router_state = {
                    "dropped": self.router.dropped.copy(),
                    "flushes": np.int64(self.router.flushes),
                }
            tree = {
                "ss": ss_K,
                "keys": keys_K,
                "rt": jax.tree.map(np.asarray, self.rt),
                "policy": {
                    "since": ps.since, "best": ps.best,
                    "rollbacks": ps.rollbacks, "lost": ps.lost,
                    "best_state": best,
                },
                "router": router_state,
                "history": {"steps": hsteps, "acc": haccs},
            }
            has_tun = self.tuner is not None and self.tuner.calibrated
            if has_tun:
                tree["tunable"] = {
                    "order": self.tuner.order,
                    "score": self.tuner.score,
                    "weights": self.tuner.weights,  # None when unit
                }
            extra = {
                "service": self._service_manifest(),
                "has_best_state": best is not None,
                "has_tunable": has_tun,
                "tunable_weighted": has_tun and self.tuner.weights is not None,
                "tunable_scored": has_tun and self.tuner.score is not None,
                "tunable_budget": (float(self.tuner.budget)
                                   if self.tuner is not None else None),
            }
            if step is None:
                step = int(self.steps.max(initial=0))
            return ckpt_mod.save(directory, int(step), tree, keep=keep,
                                 extra=extra)

    def _refuse_patched_checkpoint(self) -> None:
        if self.cfg.patched:
            raise ValueError("checkpoints of patch machines (TMConfig.window) "
                             "are not supported yet; see DESIGN.md §18")

    def _service_manifest(self) -> dict:
        """JSON-able construction knobs — enough for :meth:`restore` to
        rebuild the service without the caller knowing them."""
        sc = self.sc

        def plain(v):
            if v is None or isinstance(v, (bool, int, float, str)):
                return v
            return np.asarray(v).tolist()

        return {
            "cfg": dataclasses.asdict(self.cfg),
            "replicas": sc.replicas,
            "buffer_capacity": sc.buffer_capacity,
            "chunk": sc.chunk,
            "ingress_block": sc.ingress_block,
            "packed": sc.packed,
            "history_limit": sc.history_limit,
            "s": plain(sc.s),
            "T": plain(sc.T),
            "seed": plain(sc.seed),
            "resident": sc.resident,
            "policy": {
                "analyze_every": self.policy.analyze_every,
                "rollback_threshold": self.policy.rollback_threshold,
            },
            "tunable": (None if sc.tunable is None
                        else dataclasses.asdict(sc.tunable)),
        }

    def load(self, directory: str, *, step: Optional[int] = None) -> None:
        """Restore a :meth:`save` checkpoint INTO this service. The
        service must structurally match the writer (same TMConfig,
        replicas, capacity, packing — :meth:`restore` guarantees that);
        the ``resident`` budget may differ. Anything staged or held now
        is discarded: the checkpoint defines the complete state."""
        self._refuse_patched_checkpoint()
        with self._device():
            # settle pending spills BEFORE the install clears the store —
            # a stale deferred snapshot must never land in the fresh one
            self._settle_spills()
            while self.router.take_block() is not None:
                pass  # drop staged rows (pre-restore traffic)
            man = ckpt_mod.read_manifest(directory, step=step)
            meta = man["extra"]["service"]
            if meta["replicas"] != self.n_replicas:
                raise ValueError(
                    f"checkpoint carries {meta['replicas']} replicas, "
                    f"this service has {self.n_replicas}"
                )
            if bool(meta["packed"]) != bool(self.sc.packed):
                raise ValueError(
                    "checkpoint and service disagree on the packed "
                    "datapath — ring-buffer rows are not interchangeable"
                )
            has_best = bool(man["extra"].get("has_best_state"))
            has_tun = bool(man["extra"].get("has_tunable"))
            template = {
                "ss": self._ss,
                "keys": 0,
                "rt": self.rt,
                "policy": {
                    "since": 0, "best": 0, "rollbacks": 0, "lost": 0,
                    "best_state": (TMState(ta_state=0) if has_best
                                   else None),
                },
                "router": {"dropped": 0, "flushes": 0},
                "history": {"steps": 0, "acc": 0},
            }
            if has_tun:
                template["tunable"] = {
                    "order": 0,
                    "score": (0 if man["extra"].get("tunable_scored")
                              else None),
                    "weights": (0 if man["extra"].get("tunable_weighted")
                                else None),
                }
            tree, man = ckpt_mod.restore(directory, template, step=step,
                                         device=False)
            self.rt = jax.tree.map(jnp.asarray, tree["rt"])
            pol = tree["policy"]
            self._ps = _PolicyState(
                since=np.asarray(pol["since"], dtype=np.int64),
                best=np.asarray(pol["best"], dtype=np.float64),
                rollbacks=np.asarray(pol["rollbacks"], dtype=np.int64),
                lost=np.asarray(pol["lost"], dtype=np.int64),
            )
            self._best_host = None
            if has_best:
                bank_K = np.asarray(pol["best_state"].ta_state)
                if self._res is not None:
                    self._best_host = bank_K
                else:
                    bs = TMState(ta_state=jnp.asarray(bank_K))
                    if self.mesh is not None:
                        sh = shard_mod.replica_shardings(
                            bs, self.mesh, n_replicas=self.n_replicas
                        )
                        bs = jax.tree.map(jax.device_put, bs, sh)
                    self._ps.best_state = bs
            hsteps, haccs = tree["history"]["steps"], tree["history"]["acc"]
            self.history = [
                (np.asarray(hsteps[i]), np.asarray(haccs[i]))
                for i in range(len(hsteps))
            ]
            if self.tuner is not None:
                # Ranks are per-replica durable state (§16): a calibrated
                # checkpoint restores them; an uncalibrated one resets the
                # controller (the checkpoint defines the complete state).
                if has_tun:
                    tun = tree["tunable"]
                    self.tuner.set_ranking(
                        np.asarray(tun["order"], dtype=np.int32),
                        (None if tun["weights"] is None
                         else np.asarray(tun["weights"], dtype=np.int32)),
                        score=(None if tun["score"] is None
                               else np.asarray(tun["score"],
                                               dtype=np.int32)),
                    )
                else:
                    self.tuner.order = None
                    self.tuner.weights = None
                    self.tuner.score = None
                saved_b = man["extra"].get("tunable_budget")
                if saved_b is not None:
                    self.tuner.budget = float(saved_b)
            ss_K, keys_K = tree["ss"], tree["keys"]
            with self.router.lock:
                self.router.dropped[:] = np.asarray(
                    tree["router"]["dropped"])
                self.router.flushes = int(tree["router"]["flushes"])
                self._dev_size = np.asarray(
                    ss_K.buf.size, dtype=np.int64
                ).reshape(self.n_replicas).copy()
            self._install_plane(ss_K, keys_K)

    def _install_plane(self, ss_K: SessionState, keys_K) -> None:
        """Install a full-K logical (SessionState, keys) host tree. Under
        residency the fleet re-partitions deterministically — replicas
        0..resident-1 take the slots, the rest spill — which is invisible
        to trajectories (activation is transparent)."""
        if self._res is None:
            plane = (jax.tree.map(jnp.asarray, ss_K), jnp.asarray(keys_K))
            if self.mesh is not None:
                sh = shard_mod.replica_shardings(
                    plane, self.mesh, n_replicas=self.n_replicas
                )
                plane = jax.tree.map(jax.device_put, plane, sh)
            self._ss, self._keys = plane
            return
        K, R = self.n_replicas, self.n_resident
        res = self._res
        res.store.clear()
        res.slot_of[:] = -1
        res.replica_of[:] = -1
        res.last_use[:] = 0
        host = jax.tree.map(np.asarray, (ss_K, keys_K))
        dev = jax.tree.map(lambda a: jnp.asarray(a[:R]), host)
        if self.mesh is not None:
            sh = shard_mod.replica_shardings(dev, self.mesh, n_replicas=R)
            dev = jax.tree.map(jax.device_put, dev, sh)
        self._ss, self._keys = dev
        res.assign(np.arange(R), np.arange(R))
        for rid in range(R, K):
            res.store[rid] = jax.tree.map(lambda a, _r=rid: a[_r], host)

    def _repartition(self, new_r: int) -> None:
        """Resize the device plane to ``new_r`` slots (§17
        auto-residency). The full-K logical fleet assembles host-side, a
        fresh residency map takes over at the new width, and
        :meth:`_install_plane` re-lands it — the same machinery that
        migrates checkpoints across device budgets, which is the proof
        that partitioning is not logical state: trajectories are
        bitwise unchanged across re-partitions."""
        ss_K, keys_K = self._assemble_plane()   # settles pending spills
        old = self._res
        self.n_resident = int(new_r)
        res = res_mod.ResidencyMap(self.n_replicas, self.n_resident,
                                   self.obs)
        # the autotune EWMA survives the resize (the counters live in
        # ``obs``); the LRU clock and assignment restart deterministically
        res.ewma_active = old.ewma_active
        self._res = res
        self.repartitions += 1
        self._install_plane(ss_K, keys_K)

    @classmethod
    def restore(
        cls,
        directory: str,
        *,
        step: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        eval_x=None,
        eval_y=None,
        resident: Union[int, None, str] = "saved",
    ) -> "TMService":
        """Rebuild a service from a :meth:`save` checkpoint: construction
        knobs come from the manifest, arrays from the npz. ``mesh`` and
        the eval set are runtime resources (not serialized) and are
        passed fresh; ``resident`` defaults to the saved budget and may
        be overridden (including to None) to migrate a fleet across
        device budgets — the checkpoint itself is residency-agnostic."""
        man = ckpt_mod.read_manifest(directory, step=step)
        meta = man["extra"]["service"]
        cfg = TMConfig(**meta["cfg"])
        sc = ServiceConfig(
            replicas=meta["replicas"],
            buffer_capacity=meta["buffer_capacity"],
            chunk=meta["chunk"],
            ingress_block=meta["ingress_block"],
            packed=meta["packed"],
            history_limit=meta["history_limit"],
            s=meta["s"],
            T=meta["T"],
            policy=AdaptPolicy(**meta["policy"]),
            seed=meta["seed"],
            mesh=mesh,
            resident=(meta["resident"] if resident == "saved"
                      else resident),
            tunable=(None if meta.get("tunable") is None
                     else tun_mod.TunableConfig(**meta["tunable"])),
        )
        svc = cls(cfg, tm_mod.init_state(cfg), sc,
                  eval_x=eval_x, eval_y=eval_y)
        svc.load(directory, step=step)
        return svc

    # -- observability ------------------------------------------------------

    @property
    def steps(self) -> np.ndarray:
        if self._res is None:
            return np.asarray(self._ss.step)
        self._settle_spills()
        out = np.zeros(self.n_replicas, dtype=np.int32)
        step_p = np.asarray(self._ss.step)
        m = self._res.replica_of >= 0
        out[self._res.replica_of[m]] = step_p[m]
        for rid, snap in self._res.store.items():
            out[rid] = snap[0].step
        return out

    @property
    def rng_keys(self) -> np.ndarray:
        """Per-replica RNG keys, full-K host view (raw uint32 key data)."""
        if self._res is None:
            return np.asarray(self._keys)
        _, keys_K = self._assemble_plane()
        return keys_K

    @property
    def rollbacks(self) -> np.ndarray:
        return self._ps.rollbacks

    @property
    def lost(self) -> np.ndarray:
        return self._ps.lost

    @property
    def since_analysis(self) -> np.ndarray:
        return self._ps.since
