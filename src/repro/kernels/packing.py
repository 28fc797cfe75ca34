"""Bit-packed literal layout: uint32 lanes for the boolean datapath.

The source FPGA is fast because TM state and booleanized data are *bits*:
clause evaluation is wide AND/NOT logic over literal wires, not arithmetic.
The unpacked datapath in this repo moves one int8/bool per literal — at
MNIST width (f = 784, L = 1568) every clause pass streams ~1.5 KB per
sample where 49 uint32 words carry the same information. This module
defines the packed representation and the pack/unpack boundaries; the
packed clause kernels (``ref.clause_eval_batch_replicated_packed`` and
the word-tiled Pallas kernel in ``clause_eval.py``) evaluate clauses as
``AND`` + ``popcount`` over these words.

Layout rule (DESIGN.md §13):

* **Word-major, LSB-first**: bit ``i`` of word ``w`` holds element
  ``32*w + i`` of the bit vector. A vector of ``n`` bits packs into
  ``ceil(n/32)`` uint32 words; the unused high bits of the last word
  ("tail bits") are ALWAYS zero — every packer here guarantees it, and
  the packed clause kernels rely on it (``include & ~literals`` is
  tail-safe iff the include tail is zero; the literal tail is then
  don't-care).
* **Literals pack as two feature halves**: the literal vector
  ``[x, ~x]`` (length 2f) packs as ``[pack(x), pack(~x)]`` — 2·ceil(f/32)
  words, each half independently tail-padded. This keeps the packed
  complement a pure word operation (``~words & word_mask``), so ring
  buffers and routers store *packed features* (ceil(f/32) words) and the
  drain/infer boundary derives packed literals without ever unpacking.
  Include masks over the literal axis pack with the SAME split
  (:func:`pack_include`), so bit positions line up by construction.
* **Pack/unpack boundaries**: features pack at ingress (host-side,
  :func:`pack_bits_np`, before staging) and stay packed through the ring
  buffer and every inference/analysis pass; the ONLY unpack is the
  per-datapoint feedback step inside the online drain (TA updates are
  per-literal elementwise work and need the bits). Include masks pack
  from the int8 TA banks at each drain/infer call boundary
  (``tm.ta_actions_packed``) — O(C·J·L) once per batched call vs
  O(B·C·J·L) for the evaluation it feeds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


def n_words(n_bits: int) -> int:
    """uint32 words needed for a vector of ``n_bits`` bits."""
    return -(-n_bits // WORD_BITS)


def tail_bits(n_bits: int) -> int:
    """Valid bits in the last word (32 when ``n_bits`` is word-aligned)."""
    r = n_bits % WORD_BITS
    return WORD_BITS if r == 0 else r


def tail_mask(n_bits: int) -> int:
    """Python-int mask of the valid bits in the last word."""
    return (1 << tail_bits(n_bits)) - 1


def word_mask(n_bits: int) -> jax.Array:
    """[n_words] uint32 — all-ones per word, tail bits masked off."""
    w = n_words(n_bits)
    m = np.full((w,), 0xFFFFFFFF, dtype=np.uint32)
    m[-1] = tail_mask(n_bits)
    return jnp.asarray(m)


# ---------------------------------------------------------------------------
# Generic bit packing (jax + numpy twins, asserted equal in tests)
# ---------------------------------------------------------------------------

_SHIFTS = np.arange(WORD_BITS, dtype=np.uint32)


def pack_bits(bits: jax.Array) -> jax.Array:
    """[..., n] bool -> [..., ceil(n/32)] uint32, LSB-first, tail bits zero."""
    bits = jnp.asarray(bits).astype(bool)
    n = bits.shape[-1]
    w = n_words(n)
    pad = w * WORD_BITS - n
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), dtype=bool)], axis=-1
        )
    b = bits.reshape(bits.shape[:-1] + (w, WORD_BITS)).astype(jnp.uint32)
    # Sum of distinct powers of two — exact in uint32 by construction.
    return jnp.sum(b << jnp.asarray(_SHIFTS), axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n_bits: int) -> jax.Array:
    """[..., ceil(n/32)] uint32 -> [..., n_bits] bool (pack_bits inverse)."""
    words = jnp.asarray(words, dtype=jnp.uint32)
    b = (words[..., :, None] >> jnp.asarray(_SHIFTS)) & jnp.uint32(1)
    b = b.reshape(words.shape[:-1] + (words.shape[-1] * WORD_BITS,))
    return b[..., :n_bits].astype(bool)


def pack_bits_np(bits: np.ndarray) -> np.ndarray:
    """Host-side :func:`pack_bits` twin (the router's staging boundary)."""
    bits = np.asarray(bits, dtype=bool)
    n = bits.shape[-1]
    w = n_words(n)
    pad = w * WORD_BITS - n
    if pad:
        bits = np.concatenate(
            [bits, np.zeros(bits.shape[:-1] + (pad,), dtype=bool)], axis=-1
        )
    b = bits.reshape(bits.shape[:-1] + (w, WORD_BITS)).astype(np.uint32)
    return (b << _SHIFTS).sum(axis=-1, dtype=np.uint32)


def unpack_bits_np(words: np.ndarray, n_bits: int) -> np.ndarray:
    """Host-side :func:`unpack_bits` twin."""
    words = np.asarray(words, dtype=np.uint32)
    b = (words[..., :, None] >> _SHIFTS) & np.uint32(1)
    b = b.reshape(words.shape[:-1] + (words.shape[-1] * WORD_BITS,))
    return b[..., :n_bits].astype(bool)


# ---------------------------------------------------------------------------
# The literal-axis layout: two feature halves
# ---------------------------------------------------------------------------


def lit_words(n_features: int) -> int:
    """Packed width of the literal vector [x, ~x]: 2 * ceil(f/32) words."""
    return 2 * n_words(n_features)


def pack_literals(x: jax.Array) -> jax.Array:
    """bool features [..., f] -> packed literals [..., 2*ceil(f/32)] uint32.

    Equals ``[pack_bits(x), pack_bits(~x)]`` — the two-half layout, NOT a
    contiguous pack of the [2f] vector (those differ when f % 32 != 0).
    """
    x = jnp.asarray(x).astype(bool)
    return jnp.concatenate([pack_bits(x), pack_bits(~x)], axis=-1)


def literals_from_packed(x_packed: jax.Array, n_features: int) -> jax.Array:
    """Packed features [..., ceil(f/32)] -> packed literals [..., 2*ceil(f/32)].

    The complement half is a pure word operation (``~x & word_mask``) —
    the reason literals pack as two halves: buffered packed features turn
    into packed literals without touching individual bits. Bit-identical
    to ``pack_literals(unpack_bits(x_packed, f))``.
    """
    x_packed = jnp.asarray(x_packed, dtype=jnp.uint32)
    neg = ~x_packed & word_mask(n_features)
    return jnp.concatenate([x_packed, neg], axis=-1)


def pack_include(include: jax.Array, n_features: int) -> jax.Array:
    """Include masks [..., 2f] bool -> [..., 2*ceil(f/32)] uint32.

    Same two-half split as :func:`pack_literals` so a packed include word
    and a packed literal word index the same literal positions.
    """
    include = jnp.asarray(include).astype(bool)
    pos = include[..., :n_features]
    neg = include[..., n_features:]
    return jnp.concatenate([pack_bits(pos), pack_bits(neg)], axis=-1)


def packed_row_bytes(n_features: int) -> int:
    """Bytes per packed feature row (the ingress/buffer bandwidth unit)."""
    return 4 * n_words(n_features)
