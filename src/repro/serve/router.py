"""Fleet ingress: a host-side batch router feeding the ring buffers.

Heavy-traffic serving cannot afford one jitted dispatch per datapoint
(the ROADMAP's "Fleet-scale ingress" item): a million offers/s through a
per-point ``offer`` is a million device round-trips. :class:`BatchRouter`
is the missing layer — labelled traffic accumulates in a shared numpy
staging block (``[K, B_ingress]`` rows + per-replica fill counts, no
device interaction at all) and flushes through :func:`_enqueue_rows` as
ONE jitted dispatch pushing up to ``B_ingress`` rows into every replica's
ring buffer at once. ``benchmarks/ingress.py`` gates the win (>= 4x
offers/s over the looped per-point path at K = 8; far more in practice —
the dispatch count drops by a factor of ``B_ingress``).

Acceptance is decided host-side: the router carries an exact mirror of
every replica's outstanding datapoints (device occupancy + rows in
flight to the device; only mutated by the owning
:class:`~repro.serve.service.TMService`, which keeps the mirror in sync
on drains, flushes and state swaps), so a ``submit`` can report
backpressure synchronously — same observable semantics as the old
immediate-dispatch ``offer`` — while the device enqueue happens later,
batched.

Concurrency (DESIGN.md §14): staging is DOUBLE-BUFFERED so producers and
the flushing consumer never share an array. Two pre-allocated blocks
alternate: producers fill the *active* block under :attr:`lock`, and
``take_block`` *swaps* the blocks — the filled block becomes consumer
property (stable until the consumer's transfer completes and the next
swap hands it back), the spare becomes the new active block. Any number
of producer threads may call ``stage_rows`` concurrently; ``take_block``
assumes ONE consumer at a time (``TMService.flush`` serializes consumers
behind the service's device lock).
"""
from __future__ import annotations

import threading
from functools import partial
from typing import Optional

import jax
import numpy as np

from repro.data import buffer as buf_mod
from repro.serve import obs


@partial(jax.jit, static_argnums=1)
@jax.named_scope(obs.FLUSH)
def _enqueue_rows(ss, block: int, xs, ys, counts):
    """Push up to ``counts[r]`` staged rows into EVERY replica's ring buffer.

    xs [K, B, f] bool (or [K, B, ceil(f/32)] uint32 when the fleet's
    buffers are packed — the push is dtype-agnostic), ys [K, B] i32,
    counts [K] i32 — ONE jitted dispatch
    lands a whole ingress block (rows keep their per-replica submission
    order; rows at index >= counts[r] are padding and never touch state).
    Each ring takes its block as one dense rotated write
    (:func:`repro.data.buffer.push_block`), bitwise what ``counts[r]``
    single-row pushes would leave. Returns (new session state,
    accepted-row count [K] i32).
    """
    bufs, accepted = jax.vmap(buf_mod.push_block)(
        ss.buf, xs[:, :block], ys[:, :block], counts
    )
    return ss._replace(buf=bufs), accepted


class _StageBlock:
    """One staging block: [K, B] rows + per-replica fill counts."""

    __slots__ = ("x", "y", "count")

    def __init__(self, n_replicas: int, block: int, row_shape: tuple,
                 dtype) -> None:
        self.x = np.zeros((n_replicas, block) + row_shape, dtype=dtype)
        self.y = np.zeros((n_replicas, block), dtype=np.int32)
        self.count = np.zeros(n_replicas, dtype=np.int32)


class BatchRouter:
    """Host-side staging queue between producers and the fleet's buffers.

    * ``stage_rows(xs, ys, mask, dev_size)`` — producer side: copy one row
      per masked replica into the active staging block, deciding acceptance
      against the outstanding-rows mirror (rejected rows are per-replica
      ``dropped`` backpressure events, exactly like the old per-point
      ``offer``; a single-replica offer is a one-hot mask). Replicas whose
      staging lane is full are returned as *blocked* — neither accepted nor
      dropped; the caller flushes and retries them.
    * ``take_block()`` — consumer side: swap the double-buffered blocks and
      hand the filled ``[K, B]`` block (plus fill counts) to the service
      for one ``_enqueue_rows`` dispatch. The returned arrays stay stable
      while producers fill the other block; they are recycled at the
      next-but-one ``take_block``, by which time the (single) consumer has
      finished its transfer.

    The service flushes whenever any replica's staging lane fills, and
    before every drain/inference-independent consumer step — so a lane
    never overflows and no staged row is ever reordered within its
    replica's stream. :attr:`lock` (re-entrant) guards ALL producer-side
    state: both blocks, the drop counter, and — by convention, see
    DESIGN.md §14 — the owning service's occupancy mirror.
    """

    def __init__(self, n_replicas: int, n_features: int, capacity: int,
                 block: int = 32, *, packed: bool = False):
        K = n_replicas
        self.n_replicas = K
        self.n_features = n_features
        self.capacity = capacity
        self.block = max(1, min(block, capacity))
        self.packed = packed
        if packed:
            # Packed staging (DESIGN.md §13): rows pack host-side at the
            # staging boundary, so the staging block, the flush transfer
            # AND the device ring rows all carry ceil(f/32) uint32 words
            # instead of f bools (~8x less ingress bandwidth; the flush
            # enqueue is dtype-agnostic).
            from repro.kernels.packing import n_words

            row_shape, dtype = (n_words(n_features),), np.uint32
        else:
            row_shape, dtype = (n_features,), np.dtype(bool)
        self._blocks = (_StageBlock(K, self.block, row_shape, dtype),
                        _StageBlock(K, self.block, row_shape, dtype))
        self._active = 0
        self.lock = threading.RLock()
        self.dropped = np.zeros(K, dtype=np.int64)   # backpressure events
        self.flushes = 0                             # device dispatches

    # -- producer side ------------------------------------------------------

    @property
    def staged(self) -> np.ndarray:
        """Rows staged but not yet flushed, per replica. [K] i32 (a copy)."""
        with self.lock:
            return self._blocks[self._active].count.copy()

    def lane_full(self) -> bool:
        """True when some replica's staging lane is full (flush before the
        next stage call, or it would block that replica's row for lack of
        lane space rather than true buffer backpressure)."""
        with self.lock:
            return bool(
                (self._blocks[self._active].count >= self.block).any()
            )

    def _route_rows(self, xs) -> tuple[np.ndarray, bool]:
        """Dtype-route producer rows: bool rows pass (and later pack when
        the router is packed); already-packed uint32 word rows pass through
        on a packed router and are a hard error on an unpacked one (a
        silent ``astype(bool)`` would mangle them). Returns
        (rows broadcast to [K, width], already_packed?)."""
        K = self.n_replicas
        xs = np.asarray(xs)
        if xs.dtype == np.uint32:
            if not self.packed:
                raise TypeError(
                    "uint32 rows look bit-packed (DESIGN.md §13) but this "
                    "router stages unpacked bool rows — build the service "
                    "with ServiceConfig(packed=True) or submit bool rows"
                )
            from repro.kernels.packing import n_words

            W = n_words(self.n_features)
            if xs.shape != (K, W):
                xs = np.broadcast_to(xs, (K, W))
            return xs, True
        xs = xs.astype(bool)
        if xs.shape != (K, self.n_features):
            xs = np.broadcast_to(xs, (K, self.n_features))
        return xs, False

    def stage_rows(self, xs, ys, mask,
                   dev_size) -> tuple[np.ndarray, np.ndarray]:
        """Stage one row per masked replica. Returns (accepted, blocked),
        both [K] bool.

        ``dev_size`` is the service's outstanding-rows mirror (device
        occupancy + in-flight flush rows); acceptance is
        ``dev_size + staged < capacity``, which is exactly what an
        immediate device push would have reported. A replica that has
        buffer space but a FULL staging lane comes back ``blocked`` —
        not a backpressure drop; the caller must flush and retry (under
        concurrent producers a lane can fill between anyone's check and
        stage, so this is an expected slow path, not a protocol error).
        """
        xs, already_packed = self._route_rows(xs)
        ys = np.asarray(ys, dtype=np.int32)
        if ys.shape != (self.n_replicas,):
            ys = np.broadcast_to(ys, (self.n_replicas,))
        with self.lock:
            blk = self._blocks[self._active]
            ok = mask & (dev_size + blk.count < self.capacity)
            room = blk.count < self.block
            accepted = ok & room
            blocked = ok & ~room
            idx = np.nonzero(accepted)[0]
            if idx.size:
                c = blk.count[idx]
                if self.packed and not already_packed:
                    from repro.kernels.packing import pack_bits_np

                    # Rows pack here, at the staging boundary: everything
                    # downstream (staging block, flush, ring rows) is words.
                    blk.x[idx, c] = pack_bits_np(xs[idx])
                else:
                    blk.x[idx, c] = xs[idx]
                blk.y[idx, c] = ys[idx]
                blk.count[idx] += 1
            self.dropped += mask & ~ok
        return accepted, blocked

    # -- consumer side ------------------------------------------------------

    def take_lanes(
        self, rids
    ) -> Optional[tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Take ONLY the named replicas' staged rows out of the active
        block (stable copies; their lane counts zero so producers restage
        from the front). Returns (xs [n, B, f], ys [n, B], counts [n]) or
        None when none of the named lanes holds rows.

        The scoped-flush path for :meth:`TMService.evict`: landing a few
        replicas' rows before a spill must not force a whole-fleet flush.
        Other lanes' staged rows stay exactly where they are. Like
        ``take_block`` this assumes ONE consumer (the service's device
        lock); the inactive block never holds rows outside an in-flight
        flush, so the active block is the only staged storage to scan.
        """
        with self.lock:
            blk = self._blocks[self._active]
            rids = np.asarray(rids, dtype=np.int64).reshape(-1)
            counts = blk.count[rids].copy()
            if not counts.any():
                return None
            xs = blk.x[rids].copy()
            ys = blk.y[rids].copy()
            blk.count[rids] = 0
            return xs, ys, counts

    def take_block(self) -> Optional[tuple[np.ndarray, np.ndarray, np.ndarray]]:
        """Swap the staging blocks; returns the filled (xs [K, B, f],
        ys [K, B], counts [K]) block, or None when nothing is staged.

        Producers immediately continue into the fresh block; the returned
        arrays are NOT written again until the next-but-one ``take_block``
        (single consumer: by then its transfer is done). ``counts`` is a
        copy — the caller owns it.
        """
        with self.lock:
            blk = self._blocks[self._active]
            if not blk.count.any():
                return None
            counts = blk.count.copy()
            blk.count[:] = 0
            self._active ^= 1
            self.flushes += 1
            return blk.x, blk.y, counts
