"""Inputs made from the seed, on the device, in one jitted call each.

The surrogates follow the Gaussian-mixture model of the program's
``data/synthetic.py`` (component means ``N(0, spread^2)``, mixture logits
uniform in [-0.5, 0.5], unit noise) at a dataset's published shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits and a stream number."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, seed >> 32, stream):
        key = jax.random.fold_in(key, np.uint32(word))
    return key


def _rows(kmu, kw, kc, kn, m, n, components, spread, noise):
    """``m`` rows of the mixture whose means and weights ``kmu`` and ``kw``
    draw; ``kc`` picks each row's component and ``kn`` its noise."""
    means = jax.random.normal(kmu, (components, n), jnp.float32) * spread
    logits = jax.random.uniform(kw, (components,), minval=-0.5, maxval=0.5)
    comp = jax.random.categorical(kc, logits, shape=(m,))
    return means[comp] + noise * jax.random.normal(kn, (m, n), jnp.float32)


def gmm(key, m: int, n: int, *, components: int, spread: float,
        noise: float, sharding=None) -> jax.Array:
    """``[m, n]`` float32 rows of the mixture ``key`` draws; placed by
    ``sharding``."""
    fn = jax.jit(lambda key: _rows(*jax.random.split(key, 4), m, n,
                                   components, spread, noise),
                 out_shardings=sharding)
    return fn(key)


def gmm_rows(key, mixture_key, m: int, n: int, *, components: int,
             spread: float, noise: float) -> jax.Array:
    """``m`` fresh rows (drawn with ``key``) of the mixture that
    ``mixture_key`` draws in :func:`gmm`."""
    def rows(key, mixture_key):
        kmu, kw, _, _ = jax.random.split(mixture_key, 4)
        return _rows(kmu, kw, *jax.random.split(key), m, n, components,
                     spread, noise)

    return jax.jit(rows)(key, mixture_key)


@functools.partial(jax.jit, static_argnames=("k",))
def kmeanspp(key, x, k: int) -> jax.Array:
    """K-means++ (D^2 sampling) of ``k`` centres from the rows of ``x``,
    with float32 contractions at full precision."""
    s = x.shape[0]

    def sqdist(c):
        diff = x - c[None, :]
        return jnp.sum(diff * diff, axis=1)

    key, k0 = jax.random.split(key)
    first = x[jax.random.randint(k0, (), 0, s)]
    cents = jnp.zeros((k, x.shape[1]), jnp.float32).at[0].set(first)

    def body(i, carry):
        cents, d, key = carry
        key, sub = jax.random.split(key)
        pick = jax.random.choice(sub, s, p=d / jnp.sum(d))
        cents = cents.at[i].set(x[pick])
        return cents, jnp.minimum(d, sqdist(x[pick])), key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, sqdist(first), key))
    return cents
