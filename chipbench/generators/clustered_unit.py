"""Clustered corpus with unit-length rows, made on the device from the seed.

The mixture is the one of ``repro.data.synthetic.make_dataset(kind=
"clustered")``, copied here in ``jax.random`` so that the benchmark owns it
and a later change to the program cannot move it: ``n_clusters`` centres
drawn from N(0, 4^2) per dimension, a width per cluster drawn from
U(width_lo, width_hi), and every row (and every query) a centre chosen
uniformly plus isotropic Gaussian noise at that cluster's width.  Rows and
queries are then scaled to unit length, so L2 distance over them ranks as
the angular distance of the ANN-Benchmarks sources does.

Parameters (the configuration's ``data`` group): ``n_clusters``,
``width_lo``, ``width_hi``; the corpus's own ``seed`` is the caller's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["generate", "seed_key"]


@jax.jit
def _key(lo, hi):
    return jax.random.fold_in(jax.random.key(lo), hi)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits: ``jax.random.key`` keeps only
    the low 32, so the high half is folded in.  Both halves are arguments of
    one jitted program, so no seed is compiled in as a constant and every
    seed finds the same executable in the compilation cache."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return _key(np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32))


@functools.partial(
    jax.jit,
    static_argnames=("n", "dim", "n_queries", "n_clusters", "width_lo",
                     "width_hi"),
)
def _generate(key, qkey, n, dim, n_queries, n_clusters, width_lo, width_hi):
    k_c, k_w, k_a, k_x = jax.random.split(key, 4)
    k_qa, k_q = jax.random.split(qkey)
    centres = 4.0 * jax.random.normal(k_c, (n_clusters, dim), jnp.float32)
    widths = jax.random.uniform(
        k_w, (n_clusters,), jnp.float32, width_lo, width_hi
    )

    def draw(k_assign, k_noise, rows):
        a = jax.random.randint(k_assign, (rows,), 0, n_clusters)
        noise = jax.random.normal(k_noise, (rows, dim), jnp.float32)
        x = centres[a] + noise * widths[a][:, None]
        return x / jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))

    return draw(k_a, k_x, n), draw(k_qa, k_q, n_queries)


def generate(corpus_seed: int, query_seed: int, n: int, dim: int,
             n_queries: int, params: dict):
    """(X (n, dim), Q (n_queries, dim)) float32 device arrays, in one
    jitted call.  The mixture and its rows come from ``corpus_seed``; the
    queries, drawn from the same mixture, from ``query_seed``."""
    return _generate(
        seed_key(corpus_seed), seed_key(query_seed), n=int(n), dim=int(dim),
        n_queries=int(n_queries),
        n_clusters=int(params["n_clusters"]),
        width_lo=float(params["width_lo"]),
        width_hi=float(params["width_hi"]),
    )
