"""Plain reference of the Tsetlin-machine service semantics.

Straightforward ``jax.numpy``, one machine at a time (vmapped over the few
tenants a run checks), with no kernels, packing, buffers or batching, and
nothing imported from the program. It follows the paper's datapath and the
service's documented contract:

* literals ``[x, ~x]``; a TA includes its literal when its state is above N;
* a clause fires when every included literal is 1; an empty clause fires
  while training and is silent at inference; even clauses vote +, odd -;
* per datapoint: one non-target class drawn uniformly; the target's clauses
  get feedback with probability ``(T - clip(v_y)) / 2T``, the drawn class's
  with ``(T + clip(v_n)) / 2T``; positive clauses of the target and negative
  clauses of the drawn class take Type I, the others Type II; Type I moves a
  TA of a firing clause with a true literal toward include (always, with
  boosting) and otherwise toward exclude with probability ``1/s``; Type II
  moves an excluded TA of a firing clause with a false literal toward
  include; states stay in ``[1, 2N]``;
* the service's RNG contract: tenant ``r`` starts from
  ``fold_in(PRNGKey(seed), r)`` and splits ``(persistent, chunk)`` once per
  tick; the chunk key splits into one key per row slot of the chunk; a row's
  key splits into (selection, uniforms) and selection into (class, target,
  non-target) draws, all float32;
* analysis: accuracy on the eval set, and the rollback policy of the source
  paper (§5.3.2): a tenant is due after ``analyze_every`` trained rows; a due
  tenant without a best, or above it, snapshots its bank; one below its best
  by more than the threshold restores the snapshot.

``u_dtype`` is the precision of every uniform draw and threshold. The
configuration states float32; bfloat16 is the control that must fail.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def literals(x):
    x = x.astype(bool)
    return jnp.concatenate([x, ~x], axis=-1)


def polarity(J: int):
    return jnp.where(jnp.arange(J) % 2 == 0, 1, -1).astype(jnp.int32)


def clauses(ta, lits, n_states: int, training: bool):
    """[C, J] clause outputs of one bank [C, J, L] on one literal row [L]."""
    include = ta > n_states
    fired = jnp.all(~include | lits[None, None, :], axis=-1)
    empty = ~jnp.any(include, axis=-1)
    return jnp.where(empty, training, fired)


def predict(ta, x, n_states: int):
    """Class of one row [f] under one bank: the first class of highest vote."""
    c = clauses(ta, literals(x), n_states, training=False)
    votes = jnp.sum(c.astype(jnp.int32) * polarity(ta.shape[1]), axis=-1)
    return jnp.argmax(votes)


def update(ta, x, y, key, s, T, *, n_states: int, boost: bool, u_dtype):
    """One labelled row's TA-bank update."""
    C, J, L = ta.shape
    k_sel, k_u = jax.random.split(key)
    k_neg, k_t, k_n = jax.random.split(k_sel, 3)
    lits = literals(x)
    include = ta > n_states
    out = clauses(ta, lits, n_states, training=True)
    votes = jnp.sum(out.astype(jnp.int32) * polarity(J), axis=-1)

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
    pos = polarity(J) > 0
    is_y = (jnp.arange(C) == y)[:, None]
    is_n = (jnp.arange(C) == ny)[:, None]
    type1 = is_y & (sel_t & pos)[None] | is_n & (sel_n & ~pos)[None]
    type2 = is_y & (sel_t & ~pos)[None] | is_n & (sel_n & pos)[None]

    u = jax.random.uniform(k_u, (C, J, L), jnp.float32).astype(u_dtype)
    p_strengthen = jnp.where(boost, 1.0, (s - 1.0) / s).astype(u_dtype)
    p_erase = (1.0 / s).astype(u_dtype)
    fire = out[:, :, None]
    lit = lits[None, None, :]
    d1 = jnp.where(fire & lit, (u < p_strengthen).astype(jnp.int32),
                   -(u < p_erase).astype(jnp.int32))
    d2 = (fire & ~lit & ~include).astype(jnp.int32)
    delta = (type1[:, :, None].astype(jnp.int32) * d1
             + type2[:, :, None].astype(jnp.int32) * d2)
    return jnp.clip(ta.astype(jnp.int32) + delta, 1, 2 * n_states).astype(
        ta.dtype)


class Reference:
    """The reference for one configuration (a dict from its file)."""

    def __init__(self, conf: dict, u_dtype=jnp.float32):
        m = conf["machine"]
        self.C, self.J = m["max_classes"], m["max_clauses"]
        self.f = m["n_features"]
        self.n_states = m["n_states"]
        self.boost = m["boost_true_positive"]
        self.chunk = conf["service"]["chunk"]
        self.analyze_every = conf["service"]["analyze_every"]
        self.threshold = conf["service"]["rollback_threshold"]
        self.dtype = jnp.int8 if 2 * self.n_states <= 127 else jnp.int16
        self.u_dtype = u_dtype
        step = partial(update, n_states=self.n_states, boost=self.boost,
                       u_dtype=u_dtype)
        self._step = step

        @jax.jit
        def train_rows(ta, xs, ys, keys, s, T):
            """Rows in order through one bank (offline training)."""
            def body(t, inp):
                x, y, k = inp
                return step(t, x, y, k, s, T), None
            return jax.lax.scan(body, ta, (xs, ys, keys))[0]
        self._train_rows = train_rows

        @jax.jit
        def chunk(tas, xs, ys, n, keys, s, T):
            """One tick for the checked tenants: tas [S,C,J,L], xs [S,k,f],
            ys [S,k], n [S] rows due this tick, keys [S] chunk keys."""
            def one(ta, x, y, n_r, key):
                ks = jax.random.split(key, x.shape[0])

                def body(t, inp):
                    i, xi, yi, ki = inp
                    new = step(t, xi, yi, ki, s, T)
                    return jnp.where(i < n_r, new, t), None
                idx = jnp.arange(x.shape[0])
                return jax.lax.scan(body, ta, (idx, x, y, ks))[0]
            return jax.vmap(one)(tas, xs, ys, n, keys)
        self._chunk = chunk

        @jax.jit
        def accuracy(tas, xs, ys):
            def one(ta):
                p = jax.vmap(lambda x: predict(ta, x, self.n_states))(xs)
                return jnp.mean((p == ys).astype(jnp.float32))
            return jax.vmap(one)(tas)
        self._accuracy = accuracy

        @jax.jit
        def split(keys):
            k2 = jax.vmap(jax.random.split)(keys)
            return k2[:, 0], k2[:, 1]
        self._split = split

    # -- set-up: the base bank, made by the benchmark and not the program --

    def base_bank(self, xs, ys, key, epochs: int, s: float, T: int):
        """A bank trained from the all-exclude start, ``epochs`` passes over
        the rows in order; row i of epoch e uses key ``fold_in(key, e)``
        split per row."""
        ta = jnp.full((self.C, self.J, 2 * self.f), self.n_states,
                      self.dtype)
        xs, ys = jnp.asarray(xs, bool), jnp.asarray(ys, jnp.int32)
        for e in range(epochs):
            keys = jax.random.split(jax.random.fold_in(key, e), len(xs))
            ta = self._train_rows(ta, xs, ys, keys, jnp.float32(s),
                                  jnp.int32(T))
        return ta

    # -- the replay of a run for the checked tenants -----------------------

    def replay(self, base, tenant_keys, rows_x, rows_y, schedule, eval_x,
               eval_y, s, T):
        """Replay the checked tenants through a run's ticks.

        ``tenant_keys`` [S, 2] u32 initial keys; ``rows_x``/``rows_y``: per
        tenant, its accepted rows in submission order (lists of arrays);
        ``schedule``: per tick ``(n [S] rows trained, analysed: bool)``.
        Returns dict with ``banks`` [S,C,J,L] (host), ``acc`` [n_analyses, S]
        (analyses in tick order), ``rollbacks`` [S] and ``steps`` [S].
        """
        S = len(tenant_keys)
        k = self.chunk
        s = jnp.float32(s)
        T = jnp.int32(T)
        tas = jnp.broadcast_to(jnp.asarray(base), (S,) + base.shape)
        keys = jnp.asarray(tenant_keys, jnp.uint32)
        eval_x = jnp.asarray(eval_x, bool)
        eval_y = jnp.asarray(eval_y, jnp.int32)
        pos = np.zeros(S, np.int64)
        since = np.zeros(S, np.int64)
        best = np.full(S, np.nan)
        best_bank = tas
        rollbacks = np.zeros(S, np.int64)
        accs = []
        f = rows_x[0].shape[-1] if S and len(rows_x[0]) else self.f
        for n, analysed in schedule:
            keys, chunk_keys = self._split(keys)
            n = np.asarray(n, np.int64)
            changed = n > 0
            if changed.any():
                xs = np.zeros((S, k, f), bool)
                ys = np.zeros((S, k), np.int32)
                for i in np.nonzero(changed)[0]:
                    a, b = pos[i], pos[i] + n[i]
                    xs[i, : n[i]] = rows_x[i][a:b]
                    ys[i, : n[i]] = rows_y[i][a:b]
                tas = self._chunk(tas, xs, ys, jnp.asarray(n, jnp.int32),
                                  chunk_keys, s, T)
                pos += n
            since += n
            if analysed:
                acc = np.asarray(self._accuracy(tas, eval_x, eval_y))
                accs.append(acc)
                due = since >= self.analyze_every
                since[due] = 0
                have = ~np.isnan(best)
                collapse = due & have & (acc < best - self.threshold)
                improve = due & (~have | (acc > best))
                if collapse.any():
                    tas = jnp.where(jnp.asarray(collapse)[:, None, None, None],
                                    best_bank, tas)
                    rollbacks += collapse
                if improve.any():
                    best = np.where(improve, acc, best)
                    best_bank = jnp.where(
                        jnp.asarray(improve)[:, None, None, None], tas,
                        best_bank)
        return {
            "banks": np.asarray(tas),
            "acc": np.stack(accs) if accs else np.zeros((0, S), np.float32),
            "rollbacks": rollbacks,
            "steps": pos,
        }
