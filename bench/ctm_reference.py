"""Plain reference of the Convolutional Tsetlin machine under the service.

Granmo, Glimsdal, Jiao, Goodwin, Omlin and Berge, "The Convolutional
Tsetlin Machine", arXiv:1905.09688, sections 2-3, in straightforward
``jax.numpy`` and float32, one machine at a time (vmapped over a block of
the tenants a run checks), with nothing imported from the program. What
the service does around the machine (the RNG contract of ticks and chunks,
analysis, the rollback policy) is the plain reference's
(``bench/reference.py``), which this one extends:

* a row is a ``height x width`` image, row-major; a ``wh x ww`` window
  slides over it with stride 1, giving ``py x px`` patches, numbered in
  raster order (``p = y * px + x``);
* patch ``(x, y)`` has ``wh * ww + (px - 1) + (py - 1)`` features: its
  window's pixels in row-major order, then x-bits (bit i is 1 iff i < x),
  then y-bits (the same for y); its literals are ``[features, ~features]``;
* a clause's conjunction on a patch follows the plain rule; the clause's
  output is the OR over the patches; an empty clause fires on every patch
  while training and is silent at inference;
* in training, a clause that fired on m > 0 patches takes its Type I/II
  feedback from the literals of the ``min(floor(u * m), m - 1)``-th of
  them in raster order, ``u`` being the clause's uniform for the row, drawn
  as ``uniform(fold_in(row_key, 1), (C, J))``; with m = 0 no patch is used
  (Type I then only erases, Type II does nothing); selection,
  probabilities, boosting and clipping are the plain machine's.

Departure from the paper, as the program makes it: one patch is drawn
among those the clause fired on by a uniform float, where the paper draws
one at random without naming how.

Every matrix product runs under ``jax.default_matmul_precision("highest")``:
a product of 0/1 values is then exact in float32. ``u_dtype`` is the
precision of every uniform draw and threshold; bfloat16 is the control that
must fail.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as plain

# tenants the replay computes at once: one tenant-row of the 28x28 machine
# holds [361, 20000] float32 violation counts, so a block of 8 stays near
# a gigabyte
BLOCK = 8


def patch_grid(image, window) -> tuple[int, int]:
    (h, w), (wh, ww) = image, window
    return h - wh + 1, w - ww + 1


def patch_index(image, window) -> np.ndarray:
    """[P, wh * ww] flat pixel index of each patch's window, row-major."""
    w = image[1]
    wh, ww = window
    py, px = patch_grid(image, window)
    return np.array([[(y + dy) * w + (x + dx)
                      for dy in range(wh) for dx in range(ww)]
                     for y in range(py) for x in range(px)], np.int32)


def patch_positions(image, window) -> np.ndarray:
    """[P, (px - 1) + (py - 1)] bool thermometer codes of x, then y."""
    py, px = patch_grid(image, window)
    return np.array([[i < x for i in range(px - 1)]
                     + [i < y for i in range(py - 1)]
                     for y in range(py) for x in range(px)], bool)


def patch_literals(x, image, window):
    """[P, L] literals of one image row [h * w]."""
    x = jnp.asarray(x, bool)
    feats = jnp.concatenate([x[patch_index(image, window)],
                             jnp.asarray(patch_positions(image, window))],
                            axis=-1)
    return jnp.concatenate([feats, ~feats], axis=-1)


def patch_clauses(ta, plits, n_states: int, training: bool):
    """(output [C, J], fired [P, C, J]) of one bank [C, J, L] on one row's
    patch literals [P, L]: fired[p] where every included literal of patch p
    is 1; the output is the OR over patches."""
    C, J, L = ta.shape
    include = (ta > n_states).reshape(C * J, L)
    with jax.default_matmul_precision("highest"):
        viol = jnp.matmul(1.0 - plits.astype(jnp.float32),
                          include.astype(jnp.float32).T)    # [P, C * J]
    fired = (viol == 0).reshape(-1, C, J)
    out = jnp.any(fired, axis=0)
    if not training:
        out = out & jnp.any(include, axis=-1).reshape(C, J)
    return out, fired


def choose(fired, u):
    """[C, J] i32: the ``min(floor(u * m), m - 1)``-th firing patch of each
    clause in raster order, m its firing patches; 0 where m = 0."""
    m = jnp.sum(fired, axis=0, dtype=jnp.int32)
    t = jnp.floor(u * m.astype(u.dtype)).astype(jnp.int32)
    t = jnp.minimum(t, m - 1)
    cum = jnp.cumsum(fired, axis=0, dtype=jnp.int32)
    return jnp.argmax(fired & (cum == t[None] + 1), axis=0).astype(jnp.int32)


def predict(ta, x, n_states: int, image, window):
    """Class of one image row under one bank: the first of highest vote."""
    out, _ = patch_clauses(ta, patch_literals(x, image, window), n_states,
                           training=False)
    votes = jnp.sum(out.astype(jnp.int32) * plain.polarity(ta.shape[1]),
                    axis=-1)
    return jnp.argmax(votes)


def update(ta, x, y, key, s, T, *, image, window, n_states: int,
           boost: bool, u_dtype):
    """One labelled image row's TA-bank update."""
    C, J, L = ta.shape
    k_sel, k_u = jax.random.split(key)
    k_neg, k_t, k_n = jax.random.split(k_sel, 3)
    plits = patch_literals(x, image, window)
    include = ta > n_states
    out, fired = patch_clauses(ta, plits, n_states, training=True)
    u_patch = jax.random.uniform(jax.random.fold_in(key, 1), (C, J),
                                 jnp.float32).astype(u_dtype)
    lits = plits[choose(fired, u_patch)]                    # [C, J, L]
    votes = jnp.sum(out.astype(jnp.int32) * plain.polarity(J), axis=-1)

    ny = jax.random.categorical(
        k_neg, jnp.where(jnp.arange(C) != y, 0.0, -jnp.inf))
    Tf = T.astype(jnp.float32)
    v = jnp.clip(votes, -T, T).astype(jnp.float32)
    p_t = ((Tf - v[y]) / (2.0 * Tf)).astype(u_dtype)
    p_n = ((Tf + v[ny]) / (2.0 * Tf)).astype(u_dtype)
    u_t = jax.random.uniform(k_t, (J,), jnp.float32).astype(u_dtype)
    u_n = jax.random.uniform(k_n, (J,), jnp.float32).astype(u_dtype)
    sel_t = u_t < p_t
    sel_n = u_n < p_n
    pos = plain.polarity(J) > 0
    is_y = (jnp.arange(C) == y)[:, None]
    is_n = (jnp.arange(C) == ny)[:, None]
    type1 = is_y & (sel_t & pos)[None] | is_n & (sel_n & ~pos)[None]
    type2 = is_y & (sel_t & ~pos)[None] | is_n & (sel_n & pos)[None]

    u = jax.random.uniform(k_u, (C, J, L), jnp.float32).astype(u_dtype)
    p_strengthen = jnp.where(boost, 1.0, (s - 1.0) / s).astype(u_dtype)
    p_erase = (1.0 / s).astype(u_dtype)
    fire = out[:, :, None]
    d1 = jnp.where(fire & lits, (u < p_strengthen).astype(jnp.int32),
                   -(u < p_erase).astype(jnp.int32))
    d2 = (fire & ~lits & ~include).astype(jnp.int32)
    delta = (type1[:, :, None].astype(jnp.int32) * d1
             + type2[:, :, None].astype(jnp.int32) * d2)
    return jnp.clip(ta.astype(jnp.int32) + delta, 1, 2 * n_states).astype(
        ta.dtype)


def _in_blocks(fn, n_per_tenant: int):
    """``fn`` over blocks of ``BLOCK`` tenants: the bank plane and the next
    ``n_per_tenant`` operands are cut along their tenant axis, the rest
    passed whole."""
    def run(tas, *args):
        per, rest = args[:n_per_tenant], args[n_per_tenant:]
        return jnp.concatenate([
            fn(tas[a:a + BLOCK], *(p[a:a + BLOCK] for p in per), *rest)
            for a in range(0, tas.shape[0], BLOCK)])
    return run


class Reference(plain.Reference):
    """The reference for one patch-machine configuration."""

    def __init__(self, conf: dict, u_dtype=jnp.float32):
        super().__init__(conf, u_dtype)
        m = conf["machine"]
        image, window = tuple(m["image"]), tuple(m["window"])
        py, px = patch_grid(image, window)
        self.n_literals = 2 * (window[0] * window[1] + (px - 1) + (py - 1))
        step = partial(update, image=image, window=window,
                       n_states=self.n_states, boost=self.boost,
                       u_dtype=u_dtype)
        self._step = step

        @jax.jit
        def train_rows(ta, xs, ys, keys, s, T):
            def body(t, inp):
                x, y, k = inp
                return step(t, x, y, k, s, T), None
            return jax.lax.scan(body, ta, (xs, ys, keys))[0]
        self._train_rows = train_rows

        @jax.jit
        def chunk(tas, xs, ys, n, keys, s, T):
            """One tick for a block of tenants (as the plain reference's)."""
            def one(ta, x, y, n_r, key):
                ks = jax.random.split(key, x.shape[0])

                def body(t, inp):
                    i, xi, yi, ki = inp
                    new = step(t, xi, yi, ki, s, T)
                    return jnp.where(i < n_r, new, t), None
                idx = jnp.arange(x.shape[0])
                return jax.lax.scan(body, ta, (idx, x, y, ks))[0]
            return jax.vmap(one)(tas, xs, ys, n, keys)
        self._chunk = _in_blocks(chunk, 4)

        @jax.jit
        def accuracy(tas, xs, ys):
            def one(ta):
                p = jax.lax.map(
                    lambda x: predict(ta, x, self.n_states, image, window),
                    xs)
                return jnp.mean((p == ys).astype(jnp.float32))
            return jax.vmap(one)(tas)
        self._accuracy = _in_blocks(accuracy, 0)

    def base_bank(self, xs, ys, key, epochs: int, s: float, T: int):
        """As the plain reference's, over the patch literal width."""
        ta = jnp.full((self.C, self.J, self.n_literals), self.n_states,
                      self.dtype)
        xs, ys = jnp.asarray(xs, bool), jnp.asarray(ys, jnp.int32)
        for e in range(epochs):
            keys = jax.random.split(jax.random.fold_in(key, e), len(xs))
            ta = self._train_rows(ta, xs, ys, keys, jnp.float32(s),
                                  jnp.int32(T))
        return ta
