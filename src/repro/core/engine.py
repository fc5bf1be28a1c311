"""VectorSearchEngine — the framework's single public vector-search API.

Combines layout + index + pruner into the object a service embeds, and
delegates *execution mode* to the query planner (``repro.core.plan``): one
``search`` call serves single queries and batches, exact and pruned scans,
IVF routing, and the mesh-sharded distributed paths.  NumPy in, NumPy out.

    eng = VectorSearchEngine.build(X, index="ivf", pruner="adsampling")
    spec = SearchSpec(k=10, nprobe=16)
    ids, dists = eng.search(q, spec)        # single query
    res = eng.search(Q, spec)               # (B, D) batch — planner batches
    res.plan.executor, res.plan.reason      # which mode ran, and why

With a device mesh (built via ``jax.make_mesh`` or given at build time) the
planner dispatches to the ``repro.dist`` sharded executors automatically —
including the fused batched path that issues one top-k all-gather per query
batch, and for IVF engines the bucket-routed path where each query travels
only to the shards owning its top-nprobe buckets (one all-to-all + one
packed all-gather per batch; ``SearchSpec.routing="broadcast"`` opts back
into host-side routing):

    eng = VectorSearchEngine.build(X, mesh=jax.make_mesh((8,), ("data",)))
    res = eng.search(Q, SearchSpec(k=10))   # -> "batch-block-sharded"

    eng = VectorSearchEngine.build(X, index="ivf", mesh=mesh)
    res = eng.search(Q, SearchSpec(k=10, nprobe=4))  # -> "routed_bucket"

Migration from the pre-spec API (the deprecated ``search_jit``/
``search_batch`` shims have been removed; equivalents below):

    old call / kwarg                        spec/plan equivalent
    --------------------------------------  --------------------------------
    search(q, k=10)                         search(q, SearchSpec(k=10))
    search(q, k, nprobe=16)                 SearchSpec(k=k, nprobe=16)
    search_jit(q, k)                        SearchSpec(k=k, prefer_static=True)
                                            (or executor="jit-masked")
    search_batch(Q, k)                      search(Q, SearchSpec(k=k))
    dist.pdx_sharded.search_block_sharded   search(q, spec, mesh=mesh)
    dist.pdx_sharded.search_dim_sharded     search(q, spec, mesh=model_mesh)
    build(schedule=, delta_d=, sel_frac=,   SearchSpec(schedule=, delta_d=,
          group=, metric=)                    sel_frac=, group=, metric=)
                                            (build kwargs still accepted —
                                             they seed ``engine.spec``)
    rebuild store to add vectors            insert(X) -> new ids (write-head
                                              absorbs them; searched exactly
                                              by every executor immediately)
    rebuild store to remove vectors         delete(ids) (tombstones; slots
                                              poisoned + reusable)
    rebuild store to defragment             compact() (drains tombstones +
                                              write-head into lane-aligned
                                              tiles, refreshes the store's
                                              dim_means/dim_vars, rebuilds a
                                              BOND pruner on them, and
                                              recalibrates BSA's PCA from a
                                              fresh survivor sample — the
                                              live rows are re-projected in
                                              place)

Mutation upgrades the frozen ``PDXStore`` into a versioned
``core.layout.MutablePDXStore`` in place on first use; searches observe
``store.version`` through the plan trace and jitted-executor caches are
keyed on it, so no executor ever runs against stale tiles.

Pruner *algorithm* selection (``pruner="adsampling"``, ``eps0``, ``bsa_m``,
``zone_size``) stays a build-time choice: those transforms are baked into
the stored vectors.  Everything about a single query is a ``SearchSpec``.

Device-scan precision is a *spec* knob, not store state: the store keeps
f32 masters and lazily materializes a quantized device mirror per
``tiles_version`` (see ``core.layout.device_mirror``), so

    eng.search(Q, SearchSpec(scan_dtype="bf16"))   # 2x fewer scan bytes
    eng.search(Q, SearchSpec(scan_dtype="int8"))   # 4x fewer scan bytes

stream 2 or 1 bytes per dimension value through the hot loop (on a mesh,
through every shard's scan) while the top ``rerank_mult * k`` candidates
are re-ranked against the f32 masters — returned distances stay exact.
``build(scan_dtype=..., kernel=...)`` seeds the engine's default spec.

Multi-resolution cascades compose those precisions per query
(``SearchSpec.cascade``): a skinny projection mirror scans first, a packed
int4/int8 full-dimension pass covers its survivors (HBM traffic for pruned
partitions is skipped outright on the Pallas path), and the exact f32
re-rank terminates the pipeline —

    eng.search(Q, SearchSpec(cascade=("proj32:int8", "int4", "f32")))

``SearchSpec.route_dtype`` applies the same dtype policy to the IVF
centroid-routing scan.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from ..index.ivf import IVFIndex, build_ivf
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .layout import MutablePDXStore, PDXStore, build_flat_store, pdx_to_nary
from .pdxearch import SearchStats
from .plan import ExecutionPlan, execute, plan_search
from .pruners import (
    Pruner,
    make_adsampling,
    make_bond,
    make_bond_decreasing,
    make_bsa,
    make_plain_pruner,
)
from .spec import SearchResult, SearchSpec

__all__ = ["VectorSearchEngine", "SearchSpec", "SearchResult", "SearchStats"]

PRUNERS = ("linear", "adsampling", "bsa", "bond", "bond-decreasing")


def _make_pruner(
    name: str,
    X: np.ndarray,
    *,
    eps0: float,
    bsa_m: float,
    zone_size: int,
    seed: int,
) -> Pruner:
    if name == "linear":
        return make_plain_pruner()
    if name == "adsampling":
        return make_adsampling(X.shape[1], eps0=eps0, seed=seed)
    if name == "bsa":
        sample = X[: min(len(X), 65536)]
        return make_bsa(sample, m=bsa_m, seed=seed)
    if name == "bond":
        return make_bond(jnp.asarray(X.mean(axis=0)), zone_size=zone_size)
    if name == "bond-decreasing":
        return make_bond_decreasing(X.shape[1])
    raise ValueError(f"pruner must be one of {PRUNERS}, got {name!r}")


@dataclasses.dataclass
class VectorSearchEngine:
    """Store + pruner + optional IVF index + optional mesh, searched through
    the planner.  ``spec`` holds the engine's default ``SearchSpec`` (seeded
    from build kwargs); per-call specs override it."""

    store: PDXStore
    pruner: Pruner
    spec: SearchSpec = SearchSpec()
    ivf: Optional[IVFIndex] = None
    mesh: Any = None
    zone_size: int = 0          # BOND zone grouping (kept for pruner refresh)
    head_capacity: int = 256    # write-head size on mutable upgrade

    # ------------------------------------------------------------------ build
    @classmethod
    def build(
        cls,
        X: np.ndarray,
        *,
        metric: str = "l2",
        index: str = "flat",
        pruner: str = "adsampling",
        capacity: int = 1024,
        nlist: Optional[int] = None,
        eps0: float = 2.1,
        bsa_m: float = 3.0,
        zone_size: int = 0,
        schedule: str = "adaptive",
        delta_d: int = 32,
        sel_frac: float = 0.2,
        group: int = 8,
        kmeans_iters: int = 10,
        seed: int = 0,
        precomputed_ivf=None,
        spec: Optional[SearchSpec] = None,
        mesh: Any = None,
        routing: str = "bucket",
        scan_dtype: str = "f32",
        kernel: str = "auto",
        rerank_mult: int = 4,
        cascade: Optional[tuple] = None,
        route_dtype: str = "f32",
        nprobe: Optional[int] = None,
        tree="auto",
        super_k: Optional[int] = None,
        nprobe_super: Optional[int] = None,
    ) -> "VectorSearchEngine":
        X = np.ascontiguousarray(np.asarray(X, np.float32))
        pr = _make_pruner(
            pruner, X, eps0=eps0, bsa_m=bsa_m, zone_size=zone_size, seed=seed
        )
        Xt = pr.preprocess(X) if pr.needs_preprocess else X
        ivf = None
        if index == "ivf":
            nlist = nlist or max(int(np.sqrt(len(X))), 1)
            ivf = build_ivf(
                Xt, nlist, capacity=capacity, kmeans_iters=kmeans_iters,
                seed=seed, precomputed=precomputed_ivf, tree=tree,
                super_k=super_k, nprobe_super=nprobe_super,
            )
            store = ivf.store
        elif index == "flat":
            store = build_flat_store(Xt, capacity=capacity)
        else:
            raise ValueError(f"index must be 'flat' or 'ivf', got {index!r}")
        if spec is None:
            spec = SearchSpec(
                metric=metric, schedule=schedule, delta_d=delta_d,
                sel_frac=sel_frac, group=group, routing=routing,
                scan_dtype=scan_dtype, kernel=kernel,
                rerank_mult=rerank_mult, cascade=cascade,
                route_dtype=route_dtype, nprobe=nprobe,
            )
        return cls(store=store, pruner=pr, spec=spec, ivf=ivf, mesh=mesh,
                   zone_size=zone_size)

    # ----------------------------------------------------------------- search
    def search(
        self,
        q: np.ndarray,
        spec: Optional[SearchSpec] = None,
        *,
        stats: Optional[SearchStats] = None,
        mesh: Any = None,
        **overrides,
    ) -> SearchResult:
        """Search for the nearest neighbours of ``q`` under ``spec``.

        ``q`` — one (D,) query or a (B, D) batch; the result's ids/dists
        match that shape ((k,) or (B, k)).  ``spec`` defaults to the
        engine's; keyword ``overrides`` (any ``SearchSpec`` field, e.g.
        ``k=``, ``nprobe=``) apply on top of it, which also keeps the
        legacy ``search(q, k=10, nprobe=16)`` call shape working.  ``mesh``
        overrides the engine mesh for this call.  The returned
        ``SearchResult`` unpacks as ``(ids, dists)`` and carries the
        ``ExecutionPlan`` trace.
        """
        if isinstance(spec, (int, np.integer)):  # legacy positional k
            overrides.setdefault("k", spec)
            spec = None
        base = spec if spec is not None else self.spec
        if overrides:
            base = base.replace(**overrides)
        Q = jnp.asarray(q, jnp.float32)
        if Q.ndim not in (1, 2):
            raise ValueError(f"q must be (D,) or (B, D), got shape {Q.shape}")
        single = Q.ndim == 1
        Qb = Q[None, :] if single else Q
        use_mesh = mesh if mesh is not None else self.mesh
        t0 = time.perf_counter()
        with _trace.query(n_queries=Qb.shape[0], k=base.k) as qtrace:
            with _trace.span("plan"):
                plan = plan_search(
                    base, self.store, Qb.shape[0], pruner=self.pruner,
                    ivf=self.ivf, mesh=use_mesh,
                )
            if qtrace is not None:
                qtrace.attrs["executor"] = plan.executor
            before = dataclasses.replace(stats) if (
                stats is not None and _metrics.enabled()
            ) else None
            ids, dists = execute(
                plan, base, self.store, self.pruner, Qb,
                ivf=self.ivf, mesh=use_mesh, stats=stats,
            )
        if _metrics.enabled():
            B = Qb.shape[0]
            _metrics.counter(
                "repro_search_batches_total", executor=plan.executor
            )
            _metrics.counter(
                "repro_search_queries_total", float(B),
                executor=plan.executor,
            )
            _metrics.observe(
                "repro_search_latency_seconds", time.perf_counter() - t0,
                executor=plan.executor,
            )
            if before is not None:
                for kind, attr in (
                    ("total", "values_total"),
                    ("computed", "values_computed"),
                    ("avoided", "values_avoided"),
                ):
                    delta = getattr(stats, attr) - getattr(before, attr)
                    if delta:
                        _metrics.counter(
                            "repro_pruning_values_total", delta,
                            executor=plan.executor, kind=kind,
                        )
        if single:
            ids, dists = ids[0], dists[0]
        return SearchResult(ids=ids, dists=dists, spec=base, plan=plan,
                            stats=stats, trace=qtrace)

    def plan(
        self,
        q: np.ndarray,
        spec: Optional[SearchSpec] = None,
        *,
        mesh: Any = None,
    ) -> ExecutionPlan:
        """Dry-run the planner: which executor would ``search(q, spec)`` use."""
        Q = jnp.asarray(q, jnp.float32)
        n_queries = 1 if Q.ndim == 1 else Q.shape[0]
        return plan_search(
            spec if spec is not None else self.spec, self.store, n_queries,
            pruner=self.pruner, ivf=self.ivf,
            mesh=mesh if mesh is not None else self.mesh,
        )

    # --------------------------------------------------------------- mutation
    def _ensure_mutable(self) -> MutablePDXStore:
        """Upgrade the frozen store into a MutablePDXStore on first mutation
        (in place; the IVF index keeps pointing at the same store object)."""
        if not isinstance(self.store, MutablePDXStore):
            kwargs = dict(head_capacity=self.head_capacity)
            if self.ivf is not None:
                kwargs.update(
                    num_buckets=self.ivf.nlist,
                    part_counts=self.ivf.part_counts,
                )
            self.store = MutablePDXStore.from_store(self.store, **kwargs)
            if self.ivf is not None:
                self.ivf.store = self.store
        return self.store

    def _sync_ivf(self) -> None:
        """Repacks move bucket boundaries; refresh the index's view of them."""
        if self.ivf is not None and isinstance(self.store, MutablePDXStore):
            self.ivf.part_offsets = self.store.part_offsets
            self.ivf.part_counts = self.store.part_counts

    def insert(self, X: np.ndarray) -> np.ndarray:
        """Add vectors; returns their new ids (valid for ``delete`` and in
        search results).  Rows land in the store's write-head — searched
        exactly by every executor from this call on — and are drained into
        sealed PDX tiles by a later flush/``compact()``.  IVF engines assign
        each row to its nearest centroid at insert time so the repack keeps
        buckets contiguous."""
        X = np.atleast_2d(np.ascontiguousarray(np.asarray(X, np.float32)))
        store = self._ensure_mutable()
        Xt = self.pruner.preprocess(X) if self.pruner.needs_preprocess else X
        assignments = self.ivf.assign(Xt) if self.ivf is not None else None
        new_ids = store.insert(Xt, assignments=assignments)
        self._sync_ivf()
        return new_ids

    def delete(self, ids) -> int:
        """Tombstone vectors by id; returns how many were live.  Their slots
        are poisoned (never rank into a top-k) and become reusable."""
        store = self._ensure_mutable()
        removed = store.delete(ids)
        self._sync_ivf()
        return removed

    def compact(self) -> None:
        """Repack: drain tombstones + write-head into minimal lane-aligned
        tiles and refresh store metadata (dim_means/dim_vars).  Pruner
        calibration follows the surviving collection: a BOND pruner is
        rebuilt from the repacked collection means, and a BSA pruner's PCA
        is recalibrated from a fresh sample of the survivors — the stored
        vectors are rotated back through the old components and re-projected
        with the new ones in place (``replace_live_vectors``), so post-churn
        pruning power matches a freshly built engine instead of decaying
        with distribution shift.  Either way the pruner fingerprint changes,
        naturally invalidating jit caches."""
        store = self._ensure_mutable()
        store.repack()
        self._sync_ivf()
        if self.pruner.name == "bond":
            self.pruner = make_bond(
                jnp.asarray(store.dim_means), zone_size=self.zone_size
            )
        elif self.pruner.name == "bsa" and self.pruner.aux is not None:
            self._recalibrate_bsa(store)

    def _recalibrate_bsa(self, store: MutablePDXStore) -> None:
        """Refit BSA's PCA on the post-churn collection (ROADMAP follow-up:
        until now only BOND metadata refreshed on compact).  The projection
        is orthogonal, so the original-space vectors are recovered exactly
        (up to float rounding) as ``X_t @ C.T``; a fresh sample refits the
        components and residual-energy quantiles, and the store's live rows
        are re-projected in place.  IVF centroids ride along: bucket
        assignments are rotation-invariant (orthogonal transforms preserve
        L2), so only their coordinates change, never bucket membership."""
        Xt = pdx_to_nary(store)  # live vectors, old projected space, id order
        if len(Xt) < 2:
            return  # no covariance to fit; keep the current calibration
        C_old = np.asarray(self.pruner.aux["components"], np.float32)
        X_orig = Xt @ C_old.T
        sample = X_orig[: min(len(X_orig), 65536)]  # mirror build-time sampling
        new_pruner = make_bsa(
            sample, m=self.pruner.aux["m"], seed=self.pruner.aux["seed"]
        )
        store.replace_live_vectors(new_pruner.preprocess(X_orig))
        if self.ivf is not None:
            cents = new_pruner.preprocess(
                np.asarray(self.ivf.centroids) @ C_old.T
            )
            self.ivf.centroids = jnp.asarray(cents)
            self.ivf.centroid_store = build_flat_store(
                cents, capacity=self.ivf.centroid_store.capacity
            )
            if self.ivf.tree_enabled:
                # The two-level tree clusters *centroids*; re-cluster it in
                # the rotated space, keeping the configured fan-out.
                self.ivf.attach_tree(
                    int(self.ivf.super_children.shape[0]),
                    self.ivf.nprobe_super,
                    seed=self.pruner.aux["seed"],
                )
        self.pruner = new_pruner

    # --------------------------------------------------------- observability
    def metrics(self) -> dict:
        """Deterministic snapshot of the process-wide metrics registry
        (``repro.obs.metrics``) — counters, gauges, histograms.  Enable
        recording with ``repro.obs.metrics.set_enabled(True)`` or
        ``REPRO_OBS=1``; see the ``repro.obs`` docstring for the families."""
        return _metrics.get_registry().snapshot()

    def dump_trace(self, path: Optional[str] = None) -> dict:
        """Recorded ``QueryTrace`` ring as Chrome/Perfetto trace JSON
        (written to ``path`` when given; loadable at ui.perfetto.dev)."""
        return _trace.get_tracer().export_chrome(path)

    # ------------------------------------------------------------------ util
    @property
    def metric(self) -> str:
        return self.spec.metric

    @property
    def num_vectors(self) -> int:
        return self.store.num_vectors

    @property
    def dim(self) -> int:
        return self.store.dim
