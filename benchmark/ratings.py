"""Seeded synthetic ratings, made on the device in one jitted call.

The distribution is ``bench.py``'s ``synthesize_ratings`` (uniform users, a
power-law item popularity with tail exponent 1.3, a rank-8 structure + 3.0 +
N(0, 0.3), clipped to [1, 5] and quantised to half stars like the MovieLens
scale) with three departures, each for set-up time or table width:

- the seed is an argument, and the draw is JAX's counter-based generator on
  the device (NumPy's took 14 s for 20 M ratings on the host, most of it in
  ``zipf``'s rejection loop);
- item ranks are a Pareto draw rounded down, ``floor((1 - u·c)^(-1/0.3))``
  with ``c = 1 - n_items^-0.3``, so ``P(rank r) = (r^-0.3 - (r+1)^-0.3) / c``
  (about ``0.3·r^-1.3``; the head item has 19.7% of all ratings at 26,744
  items where ``zipf(1.3) % n`` gave it 25.4%); elementwise, no search;
- as ``chip_smoke.py`` does, the first ratings are re-pointed so that every
  user and every item is rated at least once and both factor tables keep
  their full width. Those ratings are never held out;
- WHO rated WHAT, and which ratings are held out, is the configuration's: it
  is drawn from the configuration's ``structure_seed`` and is the same in
  every run. ``--seed`` relabels the users and the items (a permutation of
  each) and draws every rating's value. The trainer's block tables are sized
  by the degrees (``ceil(degree / 128)`` blocks an entity), so ratings whose
  degrees moved with the seed would compile a new step program in every run
  (50 s, my chip runs, PR 23); a relabelling keeps the degrees and the cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STRUCTURE_RANK = 8
NOISE = 0.3
TAIL = 0.3  # popularity's tail exponent minus one


def device_key(seed: int):
    """The key every seeded draw on the device starts from. ``rbg`` is XLA's
    own bit generator: the same seed gives the same bits on the same chip and
    build, and 1.5 G normals take a fraction of threefry's 13 s on a v5e."""
    return jax.random.key(seed, impl="rbg")


@functools.partial(
    jax.jit, static_argnames=("n_users", "n_items", "n_ratings", "heldout_share")
)
def _synthesize(structure_key, key, *, n_users, n_items, n_ratings, heldout_share):
    k_u, k_i, k_pu, k_pi, k_h = jax.random.split(structure_key, 5)
    users = jax.random.randint(k_u, (n_ratings,), 0, n_users, jnp.int32)
    c = 1.0 - float(n_items) ** -TAIL
    u = jax.random.uniform(k_i, (n_ratings,), jnp.float32)
    ranks = jnp.floor((1.0 - u * c) ** (-1.0 / TAIL)).astype(jnp.int32)
    items = jnp.clip(ranks - 1, 0, n_items - 1)
    users = users.at[:n_users].set(jax.random.permutation(k_pu, n_users).astype(jnp.int32))
    items = items.at[:n_items].set(jax.random.permutation(k_pi, n_items).astype(jnp.int32))
    heldout = jax.random.uniform(k_h, (n_ratings,)) < heldout_share
    heldout = heldout & (jnp.arange(n_ratings) >= max(n_users, n_items))

    k_ru, k_ri, k_U, k_V, k_n = jax.random.split(key, 5)
    users = jax.random.permutation(k_ru, n_users).astype(jnp.int32)[users]
    items = jax.random.permutation(k_ri, n_items).astype(jnp.int32)[items]
    scale = 1.0 / np.sqrt(STRUCTURE_RANK)
    # one 1-D gather per structure dimension: a gathered [n_ratings, 8] pads
    # its last dimension to 128 lanes, 9.5 GB for each side at 20 M ratings
    U = jax.random.normal(k_U, (STRUCTURE_RANK, n_users), jnp.float32) * scale
    V = jax.random.normal(k_V, (STRUCTURE_RANK, n_items), jnp.float32) * scale
    vals = jnp.full((n_ratings,), 3.0, jnp.float32)
    for k in range(STRUCTURE_RANK):
        vals = vals + U[k][users] * V[k][items]
    vals = vals + NOISE * jax.random.normal(k_n, (n_ratings,), jnp.float32)
    vals = jnp.round(jnp.clip(vals, 1.0, 5.0) * 2.0) / 2.0
    return users, items, vals, heldout


def synthesize_ratings(
    structure_seed: int,
    seed: int,
    n_users: int,
    n_items: int,
    n_ratings: int,
    heldout_share: float,
):
    """Host arrays ``(users int32, items int32, ratings float32, heldout
    bool)``, each ``[n_ratings]``. The same seeds give the same arrays."""
    if n_ratings < max(n_users, n_items):
        raise ValueError("fewer ratings than entities: the tables would lose width")
    out = _synthesize(
        device_key(structure_seed),
        device_key(seed),
        n_users=n_users,
        n_items=n_items,
        n_ratings=n_ratings,
        heldout_share=float(heldout_share),
    )
    return tuple(np.asarray(x) for x in out)
