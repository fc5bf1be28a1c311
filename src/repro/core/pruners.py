"""Dimension-pruning predicates hosted by PDXearch.

Each pruner bundles:
  * ``preprocess``  — offline transform of the collection (and its inverse
    requirements on queries), e.g. ADSampling's random rotation, BSA's PCA.
  * ``transform_query`` — per-query preparation.
  * ``keep_mask(partial, d, thr)`` — the pruning predicate evaluated at a
    WARMUP/PRUNE step: True = vector still alive after seeing ``d`` dims.
  * ``is_exact`` — whether pruning preserves exact top-k (BOND does; the
    probabilistic pruners trade a bounded error for earlier pruning).

All predicates are branchless (mask-valued), matching the paper's vectorized
bounds evaluation that is "done in a loop separated from the distance
calculations" (Section 4).

References: ADSampling [Gao & Long, SIGMOD'23], BSA [Yang et al., 2024],
BOND [de Vries et al., SIGMOD'02].
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Pruner",
    "pruner_fingerprint",
    "make_plain_pruner",
    "make_adsampling",
    "make_bsa",
    "pca_components",
    "make_bond",
    "random_orthogonal",
]


def pruner_fingerprint(name: str, *params) -> str:
    """Stable identity of a pruning predicate: name + hash of its parameters.

    Two pruners with equal fingerprints have functionally identical
    ``keep_mask``/``dim_order`` closures, so jit caches (and ``SearchSpec``
    plan traces) can key on this instead of object identity — object ids are
    reused after GC, which both aliased unrelated pruners and leaked cache
    entries (see ``core.pdxearch._EXEC_CACHE``).
    """
    h = hashlib.sha1()
    for p in params:
        if isinstance(p, (np.ndarray, jax.Array)):
            a = np.asarray(p)
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        else:
            h.update(repr(p).encode())
    return f"{name}:{h.hexdigest()[:16]}"


_ANON_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class Pruner:
    name: str
    is_exact: bool
    needs_preprocess: bool
    # (X (N,D) numpy) -> transformed X; build-time.
    preprocess: Callable[[np.ndarray], np.ndarray]
    # (q (D,)) -> transformed q (jnp).
    transform_query: Callable[[jax.Array], jax.Array]
    # (partial (V,), n_dims_seen scalar, thr scalar) -> keep mask (V,) bool.
    keep_mask: Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
    # Optional query-aware dimension order: (q (D,)) -> permutation (D,) int32.
    dim_order: Optional[Callable[[jax.Array], jax.Array]] = None
    # Stable identity (name + param hash).  Factories set it; a directly
    # constructed Pruner without one gets a process-unique fallback, so two
    # hand-built pruners with different closures can never share a jit-cache
    # entry (a counter, unlike id(), is never reused after GC).
    fingerprint: str = ""
    # Factory parameters needed to rebuild/invert the transform later (e.g.
    # BSA's PCA components so compact() can recalibrate from a fresh
    # sample).  Excluded from equality/hash: the fingerprint already covers
    # identity, and the dict payload is unhashable.
    aux: Optional[dict] = dataclasses.field(
        default=None, compare=False, repr=False
    )

    def __post_init__(self):
        if not self.fingerprint:
            object.__setattr__(
                self, "fingerprint", f"{self.name}:anon{next(_ANON_IDS)}"
            )


# --------------------------------------------------------------------------
# No-op pruner: PDX linear scan (never prunes). Baseline in Figures 9/10.
# --------------------------------------------------------------------------
def make_plain_pruner() -> Pruner:
    return Pruner(
        name="linear",
        is_exact=True,
        needs_preprocess=False,
        preprocess=lambda X: X,
        transform_query=lambda q: q,
        keep_mask=lambda partial, d, thr: jnp.ones_like(partial, dtype=bool),
        fingerprint=pruner_fingerprint("linear"),
    )


# --------------------------------------------------------------------------
# ADSampling — random orthogonal projection + hypothesis-test pruning.
#
# After rotating by a random orthogonal matrix, the partial squared distance
# over the first d of D dims, scaled by D/d, is an unbiased estimator of the
# full squared distance whose error concentrates as 1/sqrt(d).  ADSampling
# prunes v when    sqrt(partial * D / d)  >  thr * (1 + eps0 / sqrt(d))
# i.e. when even an (eps0/sqrt(d))-inflated threshold is exceeded.
# --------------------------------------------------------------------------
def random_orthogonal(dim: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((dim, dim)).astype(np.float64)
    q, r = np.linalg.qr(a)
    q *= np.sign(np.diag(r))[None, :]  # fix signs -> Haar distributed
    return q.astype(np.float32)


def make_adsampling(dim: int, eps0: float = 2.1, seed: int = 0) -> Pruner:
    P = random_orthogonal(dim, seed)
    Pj = jnp.asarray(P)

    def keep_mask(partial: jax.Array, d: jax.Array, thr: jax.Array) -> jax.Array:
        d = jnp.maximum(d.astype(jnp.float32), 1.0)
        ratio = jnp.float32(dim) / d
        bound = thr * (1.0 + eps0 / jnp.sqrt(d)) ** 2  # squared-space
        return partial * ratio <= bound

    return Pruner(
        name="adsampling",
        is_exact=False,
        needs_preprocess=True,
        preprocess=lambda X: (np.asarray(X, np.float32) @ P.T),
        transform_query=lambda q: jnp.matmul(
            Pj, q, precision=jax.lax.Precision.HIGHEST
        ),
        keep_mask=keep_mask,
        fingerprint=pruner_fingerprint("adsampling", dim, eps0, seed),
        # the fused Pallas scan executors bake the hypothesis test into the
        # kernel; they need the raw eps0, not just the keep_mask closure
        aux={"eps0": eps0, "dim": dim, "seed": seed},
    )


# --------------------------------------------------------------------------
# BSA — PCA projection + error-quantile pruning.
#
# Project onto PCA components ordered by decreasing eigenvalue; the energy not
# yet seen after d dims is bounded via the per-dimension residual variances
# (Cauchy–Schwarz in the original paper; we calibrate the same bound
# empirically from the collection, which is exactly the information the paper
# stores as per-block metadata).  Prune when even the most optimistic
# completion of the partial distance exceeds the threshold:
#     partial + max(0, mu_res(d) - m * sigma_res(d))  >  thr
# ``m`` plays the paper's multiplier role (higher m = safer = later pruning).
# --------------------------------------------------------------------------
def pca_components(X_sample: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """PCA of a row sample -> ((D, D) orthonormal components as columns,
    ordered by decreasing eigenvalue; (D,) eigenvalues in that order).

    Shared by BSA (full-rank projection + residual-energy pruning) and the
    cascade's skinny projection mirror (``core.layout.projection_mirror``):
    orthonormal columns make any rank-R prefix projection a *contraction*,
    so projected L2 distances lower-bound full distances — the exact-safe
    keep test the cascade's first stage relies on."""
    X_sample = np.asarray(X_sample, dtype=np.float32)
    mean = X_sample.mean(axis=0)
    cov = np.cov((X_sample - mean).T).astype(np.float64)
    if cov.ndim == 0:  # D == 1 degenerate
        cov = cov.reshape(1, 1)
    eigval, eigvec = np.linalg.eigh(cov)
    order = np.argsort(eigval)[::-1]
    components = eigvec[:, order].astype(np.float32)  # (D, D), col = component
    return components, eigval[order]


def make_bsa(X_sample: np.ndarray, m: float = 3.0, seed: int = 0) -> Pruner:
    X_sample = np.asarray(X_sample, dtype=np.float32)
    n, dim = X_sample.shape
    components, eigval = pca_components(X_sample)

    # Residual-energy statistics per cut d: for pairwise squared distances the
    # expected残 energy in dims >= d is 2 * sum_{j>=d} lambda_j; its spread is
    # calibrated from eigenvalue tails (chi-square-like second moment).
    lam = np.maximum(eigval, 0.0)
    tail = 2.0 * np.concatenate([np.cumsum(lam[::-1])[::-1], [0.0]])  # (D+1,)
    tail_var = 8.0 * np.concatenate([np.cumsum((lam**2)[::-1])[::-1], [0.0]])
    mu_res = jnp.asarray(tail, dtype=jnp.float32)          # index by d
    sigma_res = jnp.asarray(np.sqrt(tail_var), dtype=jnp.float32)

    Cj = jnp.asarray(components)

    def keep_mask(partial: jax.Array, d: jax.Array, thr: jax.Array) -> jax.Array:
        d = jnp.clip(d.astype(jnp.int32), 0, dim)
        lower = partial + jnp.maximum(mu_res[d] - m * sigma_res[d], 0.0)
        return lower <= thr

    return Pruner(
        name="bsa",
        is_exact=False,
        needs_preprocess=True,
        preprocess=lambda X: (np.asarray(X, np.float32) @ components),
        transform_query=lambda q: jnp.matmul(
            q, Cj, precision=jax.lax.Precision.HIGHEST
        ),
        keep_mask=keep_mask,
        fingerprint=pruner_fingerprint("bsa", components, m),
        aux={"components": components, "m": m, "seed": seed},
    )


# --------------------------------------------------------------------------
# PDX-BOND — the paper's own pruner.  No preprocessing; exact.
#
# Predicate: the monotone partial distance itself (a lower bound of the full
# distance for L2/L1).  Power comes from the query-aware dimension order:
# visit dimensions by decreasing |q_d - collection_mean_d| ("distance to
# means", Figure 5), optionally grouped in contiguous zones for sequential
# access (the zone logic lives in PDXearch since it owns the step schedule).
# --------------------------------------------------------------------------
def make_bond(dim_means: jax.Array, zone_size: int = 0) -> Pruner:
    means = jnp.asarray(dim_means)
    dim = means.shape[0]

    def dim_order(q: jax.Array) -> jax.Array:
        score = jnp.abs(q - means)
        if zone_size and zone_size > 1:
            nz = dim // zone_size
            zone_score = score[: nz * zone_size].reshape(nz, zone_size).sum(axis=1)
            zrank = jnp.argsort(-zone_score)
            base = zrank[:, None] * zone_size + jnp.arange(zone_size)[None, :]
            perm = base.reshape(-1)
            if nz * zone_size < dim:  # leftover dims go last, in order
                perm = jnp.concatenate(
                    [perm, jnp.arange(nz * zone_size, dim, dtype=perm.dtype)]
                )
            return perm.astype(jnp.int32)
        return jnp.argsort(-score).astype(jnp.int32)

    return Pruner(
        name="bond",
        is_exact=True,
        needs_preprocess=False,
        preprocess=lambda X: X,
        transform_query=lambda q: q,
        keep_mask=lambda partial, d, thr: partial <= thr,
        dim_order=dim_order,
        fingerprint=pruner_fingerprint("bond", means, zone_size),
    )


def make_bond_decreasing(dim: int) -> Pruner:
    """BOND's original 'decreasing query value' criterion (Figure 5 baseline)."""

    def dim_order(q: jax.Array) -> jax.Array:
        return jnp.argsort(-q).astype(jnp.int32)

    return Pruner(
        name="bond-decreasing",
        is_exact=True,
        needs_preprocess=False,
        preprocess=lambda X: X,
        transform_query=lambda q: q,
        keep_mask=lambda partial, d, thr: partial <= thr,
        dim_order=dim_order,
        fingerprint=pruner_fingerprint("bond-decreasing", dim),
    )
