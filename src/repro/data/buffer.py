"""Cyclic online-input buffer (paper §3.5.2).

The FPGA buffers online datapoints in RAM so none are dropped while the
accuracy-analysis process stalls the consumer. Here the buffer is a fixed-shape
ring in device memory (capacity x features + head/size scalars) — bounded
memory, pure-functional, scan/vmap friendly. :func:`push` appends one row
with ``dynamic_update_slice``; :func:`push_block` lands a whole staged block
with static slices and selects only, so a vmapped fleet flush stays dense.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class RingBuffer(NamedTuple):
    data_x: jax.Array  # [capacity, f] bool — or [capacity, ceil(f/32)] uint32
                       # when the buffer stores PACKED rows (DESIGN.md §13)
    data_y: jax.Array  # [capacity] int32
    head: jax.Array    # scalar int32 — next slot to pop
    size: jax.Array    # scalar int32 — valid entries

    @property
    def capacity(self) -> int:
        return self.data_x.shape[0]


def make(capacity: int, n_features: int, *, packed: bool = False) -> RingBuffer:
    """Empty ring. ``packed=True`` stores uint32 word rows (ceil(f/32) per
    datapoint — ~1/8 the bool footprint); producers must then push rows
    already packed per :mod:`repro.kernels.packing`."""
    if packed:
        from repro.kernels import packing

        data_x = jnp.zeros((capacity, packing.n_words(n_features)),
                           dtype=jnp.uint32)
    else:
        data_x = jnp.zeros((capacity, n_features), dtype=bool)
    return RingBuffer(
        data_x=data_x,
        data_y=jnp.zeros((capacity,), dtype=jnp.int32),
        head=jnp.int32(0),
        size=jnp.int32(0),
    )


def push(buf: RingBuffer, x: jax.Array, y: jax.Array) -> tuple[RingBuffer, jax.Array]:
    """Append one datapoint. Returns (buffer, accepted?).

    A full buffer rejects the push (the FPGA would stall its producer; we
    surface the condition so the caller can apply backpressure).
    """
    cap = buf.capacity
    full = buf.size >= cap
    tail = jnp.mod(buf.head + buf.size, cap)
    new_x = jax.lax.dynamic_update_slice(
        buf.data_x, x[None].astype(buf.data_x.dtype), (tail, 0)
    )
    new_y = jax.lax.dynamic_update_slice(
        buf.data_y, y[None].astype(jnp.int32), (tail,)
    )
    out = RingBuffer(
        data_x=jnp.where(full, buf.data_x, new_x),
        data_y=jnp.where(full, buf.data_y, new_y),
        head=buf.head,
        size=jnp.where(full, buf.size, buf.size + 1),
    )
    return out, ~full


def push_block(buf: RingBuffer, xs: jax.Array, ys: jax.Array,
               count: jax.Array) -> tuple[RingBuffer, jax.Array]:
    """Append the first ``count`` rows of a staged block. Returns
    (buffer, rows accepted).

    Bitwise the same as ``count`` :func:`push` calls in order: the first
    ``n = min(count, B, capacity - size)`` rows land at ring slots
    ``(tail + i) mod capacity`` and the rest are rejected (a full ring stays
    full). The block is padded to the ring's length, rotated by ``tail``
    with a barrel shift (one static roll and select per bit of ``tail``)
    and selected into the slots it lands in — no gather, scatter, dynamic
    slice or loop, so under ``vmap`` every op stays dense over
    ``[K, capacity, ...]`` and shard-local along K.
    """
    cap = buf.capacity
    xs = xs[:cap].astype(buf.data_x.dtype)
    ys = ys[:cap].astype(jnp.int32)
    n = jnp.clip(jnp.minimum(count, cap - buf.size), 0, xs.shape[0])
    tail = jnp.mod(buf.head + buf.size, cap)

    def rotate(a):
        pad = jnp.zeros((cap - a.shape[0],) + a.shape[1:], a.dtype)
        a = jnp.concatenate([a, pad])
        for k in range((cap - 1).bit_length()):
            a = jnp.where((tail >> k) & 1, jnp.roll(a, 1 << k, axis=0), a)
        return a

    lands = jnp.mod(jnp.arange(cap, dtype=jnp.int32) - tail, cap) < n
    out = RingBuffer(
        data_x=jnp.where(lands[:, None], rotate(xs), buf.data_x),
        data_y=jnp.where(lands, rotate(ys), buf.data_y),
        head=buf.head,
        size=buf.size + n,
    )
    return out, n


def pop(buf: RingBuffer) -> tuple[RingBuffer, jax.Array, jax.Array, jax.Array]:
    """Remove the oldest datapoint. Returns (buffer, x, y, valid?).

    Popping an empty buffer returns valid=False and leaves state untouched.
    """
    empty = buf.size <= 0
    x = jax.lax.dynamic_slice(buf.data_x, (buf.head, 0), (1, buf.data_x.shape[1]))[0]
    y = jax.lax.dynamic_slice(buf.data_y, (buf.head,), (1,))[0]
    out = RingBuffer(
        data_x=buf.data_x,
        data_y=buf.data_y,
        head=jnp.where(empty, buf.head, jnp.mod(buf.head + 1, buf.capacity)),
        size=jnp.where(empty, buf.size, buf.size - 1),
    )
    return out, x, y, ~empty
