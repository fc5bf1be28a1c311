"""Clustered corpus with a decaying per-dimension spectrum, made on the
device from the seed: a stand-in for GIST image descriptors.

GIST rows are non-negative and unnormalised, and a few of their dimensions
carry most of the variance.  Here the spread of dimension d is
s_d = (1 + d)^-decay: ``n_clusters`` centres drawn as s_d * centre_scale *
|N(0, 1)| per dimension, a width per cluster drawn from U(width_lo,
width_hi), and every row (and every query) a centre chosen uniformly plus
Gaussian noise of spread s_d times that cluster's width, folded to its
absolute value so that no value is negative.  Rows are not scaled.  L2
distance ranks them as the source's Euclidean distance does.

Parameters (the configuration's ``data`` group): ``n_clusters``,
``decay``, ``centre_scale``, ``width_lo``, ``width_hi``; the corpus's own
``seed`` is the caller's.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

__all__ = ["generate"]


def _seed_key():
    """``seed_key`` of the unit-length generator, so that a seed of up to
    64 bits makes the same key in both."""
    path = Path(__file__).with_name("clustered_unit.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench._by_name.generators.clustered_unit", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.seed_key


seed_key = _seed_key()


@functools.partial(
    jax.jit,
    static_argnames=("n", "dim", "n_queries", "n_clusters", "decay",
                     "centre_scale", "width_lo", "width_hi"),
)
def _generate(key, qkey, n, dim, n_queries, n_clusters, decay, centre_scale,
              width_lo, width_hi):
    k_c, k_w, k_a, k_x = jax.random.split(key, 4)
    k_qa, k_q = jax.random.split(qkey)
    spread = (1.0 + jnp.arange(dim, dtype=jnp.float32)) ** -decay
    centres = centre_scale * spread * jnp.abs(
        jax.random.normal(k_c, (n_clusters, dim), jnp.float32))
    widths = jax.random.uniform(
        k_w, (n_clusters,), jnp.float32, width_lo, width_hi
    )

    def draw(k_assign, k_noise, rows):
        a = jax.random.randint(k_assign, (rows,), 0, n_clusters)
        noise = jax.random.normal(k_noise, (rows, dim), jnp.float32)
        return jnp.abs(centres[a] + noise * spread * widths[a][:, None])

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
        decay=float(params["decay"]),
        centre_scale=float(params["centre_scale"]),
        width_lo=float(params["width_lo"]),
        width_hi=float(params["width_hi"]),
    )
