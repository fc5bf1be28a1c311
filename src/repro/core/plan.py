"""Query planner + executor registry — *how* a ``SearchSpec`` executes.

``plan_search`` maps (spec, store, query count, optional mesh) onto one of
the registered executors; ``execute`` runs the chosen plan.  All executors
answer the same question — top-k under the spec's metric/pruner config —
and differ only in execution strategy:

  adaptive             host-orchestrated PDXearch (paper Section 4); the
                       only executor with per-query IVF routing.
  jit-masked           shape-static masked PDXearch (whole search jittable).
  batch-matmul         exact MXU scan of a (B, D) query batch.
  block-sharded        PDX partitions sharded over the mesh "data" axis;
                       per-query top-k all-gather.
  dim-sharded          dimension slices sharded over the mesh "model" axis;
                       psum completes distances.
  batch-block-sharded  batch-matmul fused with block sharding: ONE packed
                       top-k all-gather per query *batch* (the ROADMAP's
                       "batched distributed search").
  routed_bucket        bucket-owned sharding (IVF + "data" mesh): queries
                       travel to the shards owning their top-nprobe buckets
                       via one all-to-all, each shard scans only its owned
                       buckets (masked per query), candidates merge
                       hierarchically through one packed all-gather.
  fused-scan           the ``repro.kernels`` megakernel: ONE Pallas grid
                       over (partition, d-tile) with the ADSampling test
                       fused per tile, streaming the store's device mirror
                       at ``spec.scan_dtype`` width (bf16/int8 operands
                       dequantized in-register).
  fused-batch          the quantized MXU batch kernel over the mirror —
                       the batched counterpart of fused-scan.

Both fused executors re-rank the top ``rerank_mult * k`` candidates
against the f32 master tiles whenever ``scan_dtype != "f32"``, so returned
distances stay exact; ``spec.kernel`` picks the Pallas kernels or their
jnp twin bodies (same contract, XLA-fused).

Planner rules, in order: a forced ``spec.executor`` wins; an IVF index on
a "data"-axis mesh routes by bucket ownership (unless
``spec.routing="broadcast"`` keeps routing host-side); a usable mesh picks
a sharded executor (batched when B > 1 and ``spec.batch_collectives``) —
on the mesh, a non-f32 ``scan_dtype`` flows *into* the batched/routed
sharded executors (quantized shard scan + on-shard f32 re-rank) rather
than changing the dispatch, while the per-query block-/dim-sharded paths
scan the f32 masters and say so in their plan reason;
otherwise a Pallas-eligible spec (``kernel="pallas"``, a TPU backend with
``kernel="auto"``, or any reduced-precision ``scan_dtype``) picks a fused
executor, batches take the MXU scan and single queries the adaptive (or,
with ``spec.prefer_static``, the masked) path.  Every fallback records its
reason in the ``ExecutionPlan`` trace.  A stats request no longer changes
dispatch: every executor accounts ``SearchStats`` work now — exactly on
the pruned paths (adaptive, jit-masked, block-sharded, fused-scan), as
full-scan totals on the exact paths, and per selected bucket on the routed
path — so ``pruning_power`` is observable wherever a query lands.

When observability is on (``repro.obs``), ``execute`` wraps the executor
body in a ``scan`` span and the write-head merge in a ``merge`` span,
executors record ``repro_device_bytes_total`` from the mirror dtype and
executed plan, and the placement cache counts hits/misses — see the
``repro.obs`` package docstring for the full metric/span taxonomy.

Tile->shard mappings are ``repro.dist.placement.Placement`` values, cached
on the store per ``(tiles_version, n_shards, kind)`` — arranging + padding
copies the tiles, which must cost once per sealed-tile mutation, not once
per search, and the dict key means the same store serving two mesh sizes
(or both a block and a bucket layout) never thrashes the cache.

Mutable stores (``core.layout.MutablePDXStore``) flow through the same
planner: the plan trace records ``store.version`` (so a cached/compared
plan is visibly tied to the tiles it saw), ``execute`` merges the store's
unflushed write-head rows *exactly* (never pruned) into every executor's
top-k, and the block-sharded executors pad the partition axis with empty
tiles when churn has left it indivisible by the mesh — a mutable store
never falls off the sharded fast path just because a repack changed P.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .distance import nary_distance, pdx_distance
from .layout import (
    BucketCache,
    DeviceMirror,
    MutablePDXStore,
    PDXStore,
    device_mirror,
    projection_mirror,
)
from .pdxearch import SearchStats, pdxearch, pdxearch_jit, search_batch_matmul
from .pruners import Pruner
from .spec import SearchSpec, parse_cascade_stage
from .topk import (
    TopK,
    rerank_positions,
    scan_topk,
    topk_from_batch,
    topk_init,
    topk_merge,
    topk_threshold,
)

__all__ = [
    "ExecutionPlan",
    "PreparedSearch",
    "executor_names",
    "plan_search",
    "execute",
    "prepare_execute",
    "pow2_bucket",
    "warm_shapes",
    "register_executor",
]


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Plan trace: which executor runs, and why the planner picked it."""

    executor: str
    reason: str
    n_queries: int
    pruner: str = ""            # pruner fingerprint (stable identity)
    mesh_axes: tuple = ()
    store_version: int = 0      # MutablePDXStore.version (frozen stores: 0)


# -------------------------------------------------------------------- registry
# name -> fn(store, pruner, Q(B,D), spec, *, ivf, mesh, stats) -> (ids, dists)
# with ids/dists shaped (B, k).
_EXECUTORS: dict[str, Callable] = {}


def register_executor(name: str):
    def deco(fn):
        _EXECUTORS[name] = fn
        return fn
    return deco


def executor_names() -> tuple[str, ...]:
    return tuple(_EXECUTORS)


def pow2_bucket(n: int, cap: Optional[int] = None) -> int:
    """Smallest power of two >= ``n`` (clamped to ``cap`` when given) — the
    compiled-shape batch buckets of the serving tier, the same demand-octave
    discipline ``dist.routing.plan_routing`` applies to send budgets: a
    drifting load cycles through at most ``log2(cap) + 1`` distinct executor
    shapes instead of minting one per batch size."""
    if n < 1:
        raise ValueError(f"batch size must be >= 1, got {n}")
    b = 1
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


# --------------------------------------------------------------------- planner
def plan_search(
    spec: SearchSpec,
    store: PDXStore,
    n_queries: int,
    *,
    pruner: Optional[Pruner] = None,
    ivf=None,
    mesh=None,
) -> ExecutionPlan:
    """Choose an executor for ``n_queries`` queries against ``store``."""
    fp = pruner.fingerprint if pruner is not None else ""
    axes = tuple(getattr(mesh, "axis_names", ())) if mesh is not None else ()
    version = getattr(store, "version", 0)

    def plan(executor: str, reason: str) -> ExecutionPlan:
        # don't drop spec knobs silently: record exactly what the chosen
        # executor honors.  Only the fused executors run Pallas bodies,
        # and only these five scan a reduced-precision device mirror.
        mirror_ok = executor in (
            "fused-scan", "fused-batch", "batch-block-sharded",
            "routed_bucket", "cascade-scan", "cascade-batch", "tiered-scan",
            "routed_tiered",
        )
        if spec.kernel == "pallas" and not (
            executor.startswith("fused")
            or executor in ("cascade-scan", "cascade-batch")
        ):
            reason += " (kernel='pallas' noted: this executor runs jnp bodies)"
        if spec.scan_dtype != "f32" and not mirror_ok:
            reason += (
                f" (scan_dtype={spec.scan_dtype!r} ignored: this executor "
                "scans the f32 masters)"
            )
        if spec.hbm_slots is not None and executor not in (
            "tiered-scan", "routed_tiered"
        ):
            reason += (
                " (hbm_slots ignored: tiered serving needs an IVF index "
                "and this executor scans a fully-resident store/mirror)"
            )
        if spec.cascade is not None and executor not in (
            "cascade-scan", "cascade-batch"
        ):
            reason += (
                " (cascade ignored: only the host-side cascade executors "
                "run stage pipelines)"
            )
        return ExecutionPlan(
            executor=executor, reason=reason, n_queries=n_queries,
            pruner=fp, mesh_axes=axes, store_version=version,
        )

    if spec.executor is not None:
        if spec.executor not in _EXECUTORS:
            raise ValueError(
                f"unknown executor {spec.executor!r}; "
                f"registered: {executor_names()}"
            )
        return plan(spec.executor, "forced by spec.executor")

    if mesh is not None:
        if ivf is not None:
            if "data" in axes and spec.routing == "bucket":
                n_sh = mesh.shape["data"]
                if spec.hbm_slots is not None:
                    return plan(
                        "routed_tiered",
                        f"mesh 'data' axis ({n_sh} shards) + IVF + "
                        f"hbm_slots={spec.hbm_slots}: region-split bucket "
                        f"cache, shard-local pool scan + one packed top-k "
                        f"all-gather, exact host-RAM re-rank "
                        f"(nprobe={spec.nprobe})",
                    )
                return plan(
                    "routed_bucket",
                    f"mesh 'data' axis ({n_sh} shards) + IVF: bucket-owned "
                    f"placement, all-to-all query routing + hierarchical "
                    f"top-k merge (nprobe={spec.nprobe})",
                )
            note = (
                "mesh ignored: spec.routing='broadcast' keeps IVF bucket "
                "routing host-side; "
                if "data" in axes
                else f"mesh ignored: IVF bucket routing needs a 'data' axis, "
                     f"mesh has {axes}; "
            )
            return _host_plan(spec, n_queries, ivf, plan, note=note)
        if "data" in axes:
            n_sh = mesh.shape["data"]
            divisible = store.num_partitions % n_sh == 0
            # a mutable store's partition count drifts with churn; the block
            # executors pad it with empty tiles, so it stays on the fast path
            if divisible or isinstance(store, MutablePDXStore):
                pad_note = (
                    "" if divisible
                    else f" (P={store.num_partitions} padded to divisibility)"
                )
                if n_queries > 1 and spec.batch_collectives:
                    return plan(
                        "batch-block-sharded",
                        f"mesh 'data' axis ({n_sh} shards), batch of "
                        f"{n_queries}: one top-k all-gather per batch"
                        + pad_note,
                    )
                return plan(
                    "block-sharded",
                    f"mesh 'data' axis ({n_sh} shards): per-query "
                    "shard-local PDXearch + top-k all-gather" + pad_note,
                )
            return _host_plan(
                spec, n_queries, ivf, plan,
                note=f"mesh ignored: {store.num_partitions} partitions not "
                     f"divisible over {n_sh} 'data' shards; ",
            )
        if "model" in axes:
            n_sh = mesh.shape["model"]
            if store.dim % n_sh == 0:
                return plan(
                    "dim-sharded",
                    f"mesh 'model' axis ({n_sh} shards): dimension-slab "
                    "partial distances + psum",
                )
            return _host_plan(
                spec, n_queries, ivf, plan,
                note=f"mesh ignored: D={store.dim} not divisible over "
                     f"{n_sh} 'model' shards; ",
            )
        return _host_plan(
            spec, n_queries, ivf, plan,
            note=f"mesh ignored: no 'data'/'model' axis in {axes}; ",
        )

    return _host_plan(spec, n_queries, ivf, plan)


def _resolve_pallas(spec: SearchSpec) -> bool:
    """Does ``spec.kernel`` resolve to the Pallas bodies here?"""
    if spec.kernel == "pallas":
        return True
    if spec.kernel == "jnp":
        return False
    return jax.default_backend() == "tpu"


def _wants_fused(spec: SearchSpec) -> bool:
    """A spec opts into the fused mirror-scanning executors by forcing the
    Pallas kernels, by running on a TPU backend with ``kernel="auto"``, or
    by requesting any reduced-precision scan (which only they honor
    host-side)."""
    return (
        spec.kernel == "pallas"
        or spec.scan_dtype != "f32"
        or (spec.kernel == "auto" and jax.default_backend() == "tpu")
    )


def _host_plan(spec, n_queries, ivf, plan, note: str = "") -> ExecutionPlan:
    if spec.hbm_slots is not None and ivf is not None:
        return plan(
            "tiered-scan",
            note + f"hbm_slots={spec.hbm_slots}: bucket-granular HBM cache "
                   f"over the routed set (scan_dtype={spec.scan_dtype}, "
                   f"nprobe={spec.nprobe}), exact host-RAM re-rank",
        )
    if spec.cascade is not None:
        body = "pallas" if _resolve_pallas(spec) else "jnp"
        where = "IVF-routed START, " if ivf is not None else ""
        if n_queries > 1:
            return plan(
                "cascade-batch",
                note + f"multi-resolution cascade {'→'.join(spec.cascade)} "
                       f"batched over the MXU ({where}kernel={body}, "
                       f"B={n_queries})",
            )
        return plan(
            "cascade-scan",
            note + f"multi-resolution cascade {'→'.join(spec.cascade)} "
                   f"({where}kernel={body}, B={n_queries})",
        )
    if _wants_fused(spec):
        body = "pallas" if _resolve_pallas(spec) else "jnp"
        if n_queries == 1 and spec.metric == "l2":
            where = "IVF-routed START, " if ivf is not None else ""
            return plan(
                "fused-scan",
                note + f"fused megakernel mirror scan ({where}scan_dtype="
                       f"{spec.scan_dtype}, kernel={body})",
            )
        extra = "; IVF store scanned exactly, all buckets" if ivf else ""
        return plan(
            "fused-batch",
            note + f"fused batched mirror scan (scan_dtype={spec.scan_dtype}"
                   f", kernel={body}, B={n_queries}){extra}",
        )
    if n_queries > 1 and ivf is None:
        return plan("batch-matmul",
                    note + f"batch of {n_queries} on one host: exact MXU scan")
    if spec.prefer_static and ivf is None:
        return plan("jit-masked",
                    note + "prefer_static: shape-static masked PDXearch")
    where = "IVF-routed" if ivf is not None else "flat"
    return plan("adaptive", note + f"{where} host-orchestrated PDXearch")


# ------------------------------------------------------------------- execution
def execute(
    plan: ExecutionPlan,
    spec: SearchSpec,
    store: PDXStore,
    pruner: Pruner,
    Q: jax.Array,
    *,
    ivf=None,
    mesh=None,
    stats: Optional[SearchStats] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Run ``plan`` for the (B, D) query batch ``Q`` -> (B, k) ids/dists.

    For mutable stores this is also the write-head merge point: whatever
    executor ran over the sealed tiles, the unflushed write-head rows are
    scanned exactly (never pruned — they carry no pruner metadata yet) and
    merged into every query's top-k, so freshly inserted vectors are
    reachable through all executors, sharded paths included.
    """
    fn = _EXECUTORS[plan.executor]
    with _trace.span("scan", executor=plan.executor,
                     scan_dtype=spec.scan_dtype):
        ids, dists = fn(
            store, pruner, Q, spec, ivf=ivf, mesh=mesh, stats=stats
        )
    with _trace.span("merge", executor=plan.executor):
        return _merge_write_head(
            store, pruner, Q, spec, np.asarray(ids), np.asarray(dists),
            stats=stats,
        )


@dataclasses.dataclass
class PreparedSearch:
    """The host half of one planned batch; ``run()`` performs the device
    half.  Produced by ``prepare_execute`` so a serving loop can overlap
    batch N+1's host-side planning (routing, send-buffer packing,
    placement/cache lookups) with batch N's device collectives — the
    double-buffering in ``repro.serve.vector``.  ``run()`` must be called
    exactly once, and the store must not be mutated between ``prepare``
    and ``run`` (the serving loop serializes both under its store lock /
    executor thread)."""

    plan: ExecutionPlan
    spec: SearchSpec
    _run: Callable[[], tuple[np.ndarray, np.ndarray]]

    def run(self) -> tuple[np.ndarray, np.ndarray]:
        return self._run()


def prepare_execute(
    plan: ExecutionPlan,
    spec: SearchSpec,
    store: PDXStore,
    pruner: Pruner,
    Q: jax.Array,
    *,
    ivf=None,
    mesh=None,
    stats: Optional[SearchStats] = None,
) -> PreparedSearch:
    """Split ``execute`` into host preparation (now) and device execution
    (``PreparedSearch.run()``, later).

    For ``routed_bucket`` the split is genuine: placement lookup, batch
    transform, bucket ranking, exchange planning, and send-buffer packing
    all happen here, and ``run()`` only fires the collectives.  For every
    other executor the host share is negligible, so the whole ``execute``
    is deferred into ``run()`` — callers get one uniform contract."""
    if plan.executor == "routed_bucket":
        launch, sel = _prepare_routed_host(
            store, pruner, Q, spec, ivf=ivf, mesh=mesh
        )

        def _run():
            with _trace.span("scan", executor=plan.executor,
                             scan_dtype=spec.scan_dtype):
                ids, dists = _run_routed_device(
                    launch, sel, store, spec, ivf=ivf, stats=stats
                )
            with _trace.span("merge", executor=plan.executor):
                return _merge_write_head(
                    store, pruner, Q, spec, ids, dists, stats=stats
                )

        return PreparedSearch(plan=plan, spec=spec, _run=_run)

    if plan.executor in ("tiered-scan", "routed_tiered"):
        # the host half ends with the first chunk's ensure() — the cache
        # uploads of batch N+1 overlap batch N's device scan through the
        # serving loop's depth-1 handoff (routing-driven prefetch)
        if plan.executor == "tiered-scan":
            tl = _prepare_tiered_host(store, pruner, Q, spec, ivf=ivf)
            runner = lambda: _run_tiered_device(        # noqa: E731
                tl, store, spec, ivf=ivf, stats=stats
            )
        else:
            tl = _prepare_routed_tiered_host(
                store, pruner, Q, spec, ivf=ivf, mesh=mesh
            )
            runner = lambda: _run_routed_tiered_device(  # noqa: E731
                tl, store, spec, ivf=ivf, mesh=mesh, stats=stats
            )

        def _run_tiered():
            with _trace.span("scan", executor=plan.executor,
                             scan_dtype=spec.scan_dtype):
                ids, dists = runner()
            with _trace.span("merge", executor=plan.executor):
                return _merge_write_head(
                    store, pruner, Q, spec, ids, dists, stats=stats
                )

        return PreparedSearch(plan=plan, spec=spec, _run=_run_tiered)

    return PreparedSearch(
        plan=plan, spec=spec,
        _run=lambda: execute(
            plan, spec, store, pruner, Q, ivf=ivf, mesh=mesh, stats=stats
        ),
    )


def warm_shapes(
    spec: SearchSpec,
    store: PDXStore,
    pruner: Pruner,
    buckets,
    *,
    ivf=None,
    mesh=None,
) -> dict:
    """Pre-compile the executor for each batch-shape bucket by pushing one
    real synthetic batch per bucket through ``prepare_execute().run()`` —
    seeding the jit shape caches, the placement/mirror caches, and (for
    mutable stores) the static-shape write-head merge, so a serving loop's
    steady state mints no new executables.  Returns {bucket: executor}.

    On a routed mesh the all-to-all budget is data-dependent (a demand
    octave per skew level); the warmup batch spreads queries across the
    batch index, which warms the common low-demand octave — the first
    heavily skewed batch may still compile its (single) spilled shape."""
    out = {}
    D = store.dim
    rng = np.random.default_rng(0)
    for b in sorted(set(int(x) for x in buckets)):
        Qb = rng.standard_normal((b, D)).astype(np.float32)
        plan = plan_search(
            spec, store, b, pruner=pruner, ivf=ivf, mesh=mesh
        )
        prepare_execute(
            plan, spec, store, pruner, jnp.asarray(Qb), ivf=ivf, mesh=mesh
        ).run()
        if getattr(store, "head_capacity", None):
            # churn serving inserts into the head mid-stream: warm the
            # (bucket, head_capacity) merge executable even while empty
            H = jnp.full((store.head_capacity, D), 0.0, jnp.float32)
            Qt = _transform_batch(pruner, jnp.asarray(Qb))
            _head_distances(H, Qt, spec.metric)
        out[b] = plan.executor
    return out


@functools.partial(jax.jit, static_argnames=("metric",))
def _head_distances(H, Qt, metric):
    """(H_cap, D) full head buffer x (B, D) queries -> (B, H_cap) distances.
    Shape-static in the head CAPACITY, not the live count: under serving
    churn the fill level changes every insert, and a fill-shaped trace
    would mint one executable per distinct fill — this is one executable
    per (B, head_capacity) pair, warmed once by ``warm_shapes``."""
    return jax.vmap(lambda q: nary_distance(H, q, metric))(Qt)


def _merge_write_head(
    store, pruner: Pruner, Q: jax.Array, spec: SearchSpec,
    ids: np.ndarray, dists: np.ndarray,
    stats: Optional[SearchStats] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Merge the store's live write-head rows into the (B, k) top-k — exact,
    unpruned, in the pruner-transformed space the sealed tiles live in.

    The distance pass runs over the FULL head buffer (dead rows masked to
    +inf host-side) so its compiled shape depends only on ``head_capacity``
    and the batch bucket — never on the drifting fill level."""
    head_snapshot = getattr(store, "head_snapshot", None)
    if head_snapshot is None:
        return ids, dists
    hids, hvecs = head_snapshot()                    # full (H,), (H, D)
    live = hids >= 0
    m = int(live.sum())
    if m == 0:
        return ids, dists
    Qt = _transform_batch(pruner, Q)                             # (B, D)
    hd = np.asarray(
        _head_distances(jnp.asarray(hvecs, jnp.float32), Qt, spec.metric)
    )  # (B, H)
    hd = np.where(live[None, :], hd, np.inf)
    if stats is not None:  # the LIVE head rows are scanned in full, unpruned
        work = float(len(Q) * m * hvecs.shape[1])
        stats.values_total += work
        stats.values_computed += work
    all_d = np.concatenate([dists.astype(np.float32), hd.astype(np.float32)],
                           axis=1)
    all_i = np.concatenate(
        [ids, np.broadcast_to(hids.astype(ids.dtype), hd.shape)], axis=1
    )
    order = np.argsort(all_d, axis=1, kind="stable")[:, : spec.k]
    return (
        np.take_along_axis(all_i, order, axis=1),
        np.take_along_axis(all_d, order, axis=1),
    )


def _exact_scan_stats(stats: Optional[SearchStats], store, B: int) -> None:
    """Work accounting for the exact full-scan executors: every live value
    is computed, nothing avoided — the honest baseline ``pruning_power``
    compares against."""
    if stats is None:
        return
    work = float(np.asarray(store.counts).sum()) * store.dim * B
    stats.values_total += work
    stats.values_computed += work
    stats.partitions_visited += store.num_partitions * B


@register_executor("adaptive")
def _exec_adaptive(store, pruner, Q, spec, *, ivf, mesh, stats):
    out_i, out_d = [], []
    for q in Q:
        if ivf is not None:
            with _trace.span("route", nprobe=spec.nprobe):
                qt = pruner.transform_query(q)
                order, start_parts = ivf.route(
                    qt, spec.nprobe, spec.metric, spec.route_dtype
                )
        else:
            order, start_parts = None, 1
        res = pdxearch(
            store, q, spec.k, pruner, metric=spec.metric,
            schedule=spec.schedule, delta_d=spec.delta_d,
            sel_frac=spec.sel_frac, group=spec.group,
            pid_order=order, start_parts=start_parts, stats=stats,
        )
        out_i.append(np.asarray(res.ids))
        out_d.append(np.asarray(res.dists))
    return np.stack(out_i), np.stack(out_d)


@register_executor("jit-masked")
def _exec_jit_masked(store, pruner, Q, spec, *, ivf, mesh, stats):
    if ivf is not None:
        raise ValueError(
            "jit-masked executor has no IVF routing (bucket ranking is "
            "data-dependent); use the adaptive executor"
        )
    out_i, out_d = [], []
    for q in Q:
        res = pdxearch_jit(
            store, q, spec.k, pruner, metric=spec.metric,
            schedule=spec.schedule, delta_d=spec.delta_d, stats=stats,
        )
        out_i.append(np.asarray(res.ids))
        out_d.append(np.asarray(res.dists))
    return np.stack(out_i), np.stack(out_d)


def _transform_batch(pruner: Pruner, Q: jax.Array) -> jax.Array:
    """Pruner query transforms are per-vector; vmap lifts them to batches."""
    if not pruner.needs_preprocess:
        return Q
    return jax.vmap(pruner.transform_query)(Q)


@register_executor("batch-matmul")
def _exec_batch_matmul(store, pruner, Q, spec, *, ivf, mesh, stats):
    # Exact scan over ALL partitions (IVF engines included: their store holds
    # every bucket, so this is exact; nprobe does not apply).
    Qt = _transform_batch(pruner, Q)
    res = search_batch_matmul(store.data, store.ids, Qt, spec.k, spec.metric)
    B = Q.shape[0]
    _exact_scan_stats(stats, store, B)
    if _metrics.enabled():
        P, D, C = store.data.shape
        _metrics.counter(
            "repro_device_bytes_total", float(B) * P * D * C * 4,
            executor="batch-matmul", component="scan", dtype="f32",
        )
    return np.asarray(res.ids), np.asarray(res.dists)


# ------------------------------------------------- fused mirror executors
# The merge of the kernel island into the serving stack: these are the only
# executors that stream the store's reduced-precision device mirror
# (core.layout.device_mirror) and the only callers of the repro.kernels
# Pallas ops.  Candidates are tracked as flat tile POSITIONS (p * C + c),
# not global ids, so the exact f32 re-rank can gather master columns with
# one fancy index; positions map to ids only at the end.
def _rerank_k(spec: SearchSpec, store) -> int:
    if spec.scan_dtype == "f32":
        return spec.k
    cap = store.num_partitions * store.capacity
    return min(spec.rerank_mult * spec.k, cap)


@functools.partial(
    jax.jit, static_argnames=("rk", "metric", "use_pallas", "quantized",
                              "packed", "dim")
)
def _fused_batch_scan(
    mdata, ids, Qt, scale, offset, rk, metric, use_pallas, quantized,
    packed: bool = False, dim: int | None = None,
) -> tuple[TopK, Optional[jax.Array]]:
    """Scan every mirror tile with the quantized batch kernel -> per-query
    top-``rk`` flat positions (PAD lanes carry position -1), and the flag
    of ``scan_topk``'s fallback to the per-step merge (None: merge only)."""
    from ..kernels.ops import batched_distance_quant_op
    from ..kernels.ref import dequantize_ref

    sc = scale if quantized else None
    off = offset if quantized else None

    def distances(tile):
        if metric == "l1":  # no matmul form; dequantize + vmapped VPU scan
            t32 = dequantize_ref(tile, sc, off, packed=packed, dim=dim)
            return jax.vmap(lambda q: pdx_distance(t32, q, "l1"))(Qt)
        return batched_distance_quant_op(
            tile, Qt, sc, off, metric, use_pallas, packed=packed, dim=dim,
        )

    return scan_topk(distances, mdata, ids < 0, Qt.shape[0], rk)


@jax.jit
def _positions_to_ids(store_ids, cand: TopK) -> TopK:
    safe = jnp.maximum(cand.ids, 0)
    gids = jnp.where(cand.ids >= 0, store_ids.reshape(-1)[safe], -1)
    return TopK(dists=cand.dists, ids=gids)


@register_executor("fused-batch")
def _exec_fused_batch(store, pruner, Q, spec, *, ivf, mesh, stats):
    """Exact-over-store scan of the device mirror at ``spec.scan_dtype``
    width (IVF engines included — all buckets, like batch-matmul), f32
    re-ranked when the mirror is reduced-precision."""
    mirror = device_mirror(store, spec.scan_dtype)
    with _trace.span("transform"):
        Qt = _transform_batch(pruner, jnp.asarray(Q, jnp.float32))
    rk = _rerank_k(spec, store)
    cand, fallback = _fused_batch_scan(
        mirror.data, store.ids, Qt, mirror.scale, mirror.offset,
        rk, spec.metric, _resolve_pallas(spec), mirror.quantized,
        packed=mirror.packed, dim=mirror.dim,
    )
    if spec.scan_dtype == "f32":
        res = _positions_to_ids(store.ids, cand)
    else:
        with _trace.span("rerank", rk=rk):
            res = _trace.fence(rerank_positions(
                store.data, store.ids, Qt, cand, spec.k, spec.metric
            ))
    B = Q.shape[0]
    _exact_scan_stats(stats, store, B)
    if _metrics.enabled():
        P, C = mirror.data.shape[0], mirror.data.shape[2]
        D = mirror.dim  # logical D (packed int4 halves the stored axis)
        _metrics.counter(
            "repro_device_bytes_total",
            float(B) * P * D * C * mirror.bytes_per_value,
            executor="fused-batch", component="scan", dtype=mirror.dtype,
        )
        if spec.scan_dtype != "f32":
            _metrics.counter(
                "repro_device_bytes_total", float(B) * rk * D * 4,
                executor="fused-batch", component="rerank", dtype="f32",
            )
    out = np.asarray(res.ids), np.asarray(res.dists)
    if fallback is not None and _metrics.enabled():
        # read after the answers, so the flag costs no sync of its own
        _metrics.counter(
            "repro_slot_topk_batches_total",
            outcome="fallback" if bool(fallback) else "certified",
        )
    return out


@register_executor("fused-scan")
def _exec_fused_scan(store, pruner, Q, spec, *, ivf, mesh, stats):
    """Single-query megakernel scan: ONE Pallas grid over (partition,
    d-tile), ADSampling keep-mask fused per tile, mirror operands
    dequantized in-register, dead partitions skipped whole-tile.

    The threshold is seeded by an exact f32 START scan of one partition —
    the IVF-routed nearest bucket's first partition when an index exists,
    partition 0 otherwise.  The START partition is masked OUT of the
    megakernel scan (its lanes would otherwise enter the merge pool twice
    and crowd out the k-th distinct neighbour) and its candidates merge
    exactly, unpruned — a hypothesis-test casualty there is impossible.
    Pruners other than ADSampling scan unpruned (thr = inf): they get the
    bandwidth win without a foreign predicate."""
    if spec.metric != "l2":
        raise ValueError(
            "fused-scan is L2-only (ADSampling's domain); the planner "
            "routes other metrics to fused-batch"
        )
    mirror = device_mirror(store, spec.scan_dtype)
    use_pallas = _resolve_pallas(spec)
    rk = _rerank_k(spec, store)
    prune = pruner.name == "adsampling" and pruner.aux is not None
    eps0 = float(pruner.aux["eps0"]) if prune else 2.1
    sc = mirror.scale if mirror.quantized else None
    off = mirror.offset if mirror.quantized else None
    out_i, out_d = [], []
    for q in Q:
        qt = pruner.transform_query(jnp.asarray(q, jnp.float32))
        p0 = 0
        if ivf is not None:
            order, _ = ivf.route(qt, 1, "l2", dtype=spec.route_dtype)
            if len(order):
                p0 = int(order[0])
        start = topk_from_batch(
            pdx_distance(store.data[p0], qt, "l2"), store.ids[p0], spec.k
        )
        thr = topk_threshold(start) if prune else jnp.float32(np.inf)
        res = _fused_scan_one(
            mirror.data, store.data, store.ids, jnp.int32(p0), qt, thr,
            sc, off, eps0, rk, spec.k, use_pallas,
            spec.scan_dtype == "f32", start,
            packed=mirror.packed, dim=mirror.dim,
        )
        if stats is not None:
            _fused_scan_stats(stats, store, mirror, p0, qt, thr, eps0)
        out_i.append(np.asarray(res.ids))
        out_d.append(np.asarray(res.dists))
    if spec.scan_dtype != "f32":
        # the exact re-rank runs fused inside _fused_scan_one — record it
        # as a zero-width annotation span plus its gather bytes
        with _trace.span("rerank", fused="in-kernel", rk=rk):
            pass
        _metrics.counter(
            "repro_device_bytes_total",
            float(len(Q)) * rk * store.dim * 4,
            executor="fused-scan", component="rerank", dtype="f32",
        )
    return np.stack(out_i), np.stack(out_d)


def _fused_scan_stats(stats, store, mirror, p0, qt, thr, eps0) -> None:
    """Work accounting for the megakernel: replay the per-d-tile keep-mask
    walk (``obs.meters.fused_tile_counts``) to recover how many lanes each
    tile computed — an explicit second pass over the mirror, paid only when
    stats are requested (the fused kernel itself can't count without
    spilling its mask).  The START partition is masked out of the walk and
    charged at full D, exactly mirroring the executor."""
    from ..obs import meters as _meters

    counts = np.asarray(store.counts)
    P, C = mirror.data.shape[0], mirror.data.shape[2]
    D = mirror.dim  # logical D (packed int4 halves the stored axis)
    ids_scan = store.ids.at[p0].set(-1)
    lanes, parts = _meters.fused_tile_counts(
        mirror.data, ids_scan, qt, thr, mirror.scale, mirror.offset,
        eps0=eps0, packed=mirror.packed, dim=mirror.dim,
    )
    w = _meters.tile_widths(D)
    total = float(counts.sum()) * D
    computed = float(counts[p0]) * D + float((lanes * w).sum())
    stats.values_total += total
    stats.values_computed += computed
    stats.values_avoided += total - computed
    stats.partitions_visited += P
    if _metrics.enabled():
        demand = (
            D * C * 4 + float((parts * w).sum()) * C * mirror.bytes_per_value
        )
        _metrics.counter(
            "repro_device_bytes_total", demand,
            executor="fused-scan", component="scan", dtype=mirror.dtype,
        )


@functools.partial(
    jax.jit,
    static_argnames=("eps0", "rk", "k", "use_pallas", "exact", "packed",
                     "dim"),
)
def _fused_scan_one(
    mdata, master, ids, p0, qt, thr, scale, offset, eps0, rk, k, use_pallas,
    exact, start: TopK, packed: bool = False, dim: int | None = None,
) -> TopK:
    from ..kernels.ops import pdx_prune_scan_multi_op

    P, _, C = mdata.shape
    # the START partition was scanned exactly already: kill its lanes so the
    # megakernel whole-tile-skips it and its ids never enter the pool twice
    ids_scan = ids.at[p0].set(-1)
    dists, alive = pdx_prune_scan_multi_op(
        mdata, ids_scan, qt, thr, scale, offset, eps0=eps0,
        use_pallas=use_pallas, packed=packed, dim=dim,
    )
    flat_d = jnp.where(alive, dists, jnp.inf).reshape(-1)
    cand = topk_from_batch(flat_d, jnp.arange(P * C, dtype=jnp.int32), rk)
    # dead lanes carry +inf: only real survivors are selected unless fewer
    # than rk survive, and PAD positions resolve to id -1 below either way
    if exact:
        res = _positions_to_ids(ids_scan, TopK(cand.dists, cand.ids))
    else:
        res = rerank_positions(
            master, ids_scan, qt[None],
            TopK(cand.dists[None], cand.ids[None]), k, "l2",
        )
        res = TopK(dists=res.dists[0], ids=res.ids[0])
    return topk_merge(res, start.dists, start.ids)


# ------------------------------------------------- cascade executor
def _quant_err_norm(mirror) -> float:
    """L2 norm bound of a quantized mirror's reconstruction error vector.

    Per-dimension rounding error is at most ``scale_d / 2`` (the observed-
    range affine never clips), so ``||x_hat - x|| <= 0.5 * ||scale||`` for
    every live vector.  By the triangle inequality any vector with true
    distance ``<= thr`` has dequantized distance ``<= (sqrt(thr) + err)^2``
    — the exact-safe threshold inflation the cascade's quantized keep tests
    apply (without it, int4's coarse step at high D prunes true neighbours
    wholesale)."""
    if not mirror.quantized:
        return 0.0
    return 0.5 * float(np.linalg.norm(np.asarray(mirror.scale)))


@functools.partial(
    jax.jit,
    static_argnames=("eps0", "d_tile", "use_pallas", "packed", "dim",
                     "first"),
)
def _cascade_stage(
    mdata, ids_scan, alive_prev, qs, thr, scale, offset, eps0, d_tile,
    use_pallas, packed, dim, first,
):
    """One cascade scan stage over the (P, D_i, C) stage mirror ``mdata``
    -> ``(dists, alive, streamed)``.

    Stage N+1 seeds its keep-mask from stage N's alive bitmap: dead lanes'
    ids are forced to -1, so the kernels' ``ids >= 0`` convention carries
    the mask across stages.  Later stages run through the prefetch-skip
    wrapper's *(partition, d-tile)* pair schedule: entry-dead partitions
    fetch nothing and a partition stops fetching at the d-tile where its
    last lane dies (conditional in-kernel DMA on the Pallas path).
    ``streamed`` is the per-partition fetched-d-tile count the executor
    meters as realized traffic; the first stage has every partition live
    and streams plainly (streamed = all tiles)."""
    from ..kernels.ops import (
        pdx_prune_scan_multi_op,
        pdx_prune_scan_multi_prefetch_op,
    )

    if first:
        P = mdata.shape[0]
        logical = dim if packed else mdata.shape[1]
        nd = -(-logical // min(d_tile, logical))
        dists, alive = pdx_prune_scan_multi_op(
            mdata, ids_scan, qs, thr, scale, offset, eps0=eps0,
            d_tile=d_tile, use_pallas=use_pallas, packed=packed, dim=dim,
        )
        return dists, alive, jnp.full((P,), float(nd), jnp.float32)
    ids_i = jnp.where(alive_prev, ids_scan, -1)
    return pdx_prune_scan_multi_prefetch_op(
        mdata, ids_i, qs, thr, scale, offset, eps0=eps0,
        d_tile=d_tile, use_pallas=use_pallas, packed=packed, dim=dim,
    )


@functools.partial(jax.jit, static_argnames=("k",))
def _cascade_finish(master, ids_scan, qt, alive, k, start: TopK) -> TopK:
    """Exact terminal stage: every lane the keep tests spared is re-scored
    in f32 against the master tiles and merged with the exact START
    candidates.

    The survivors of the (exact-safe, quantization-inflated) final keep
    test are exactly the lanes that could still enter the top-k, so all of
    them are re-scored: a top-rk cut by the last stage's noisy distances
    silently drops true neighbours when int4's reordering radius exceeds
    rerank_mult*k.  The loop walks only the partitions holding a survivor,
    one (D, C) master tile per step, so one executable serves every
    survivor count."""
    P, D, C = master.shape
    alive = alive.reshape(P, C)
    has = jnp.any(alive, axis=1)
    order = jnp.argsort(~has)  # stable: partitions with survivors first

    def body(i, state):
        p = order[i]
        d = jnp.where(alive[p], pdx_distance(master[p], qt, "l2"), jnp.inf)
        return topk_merge(state, d, ids_scan[p])

    res = jax.lax.fori_loop(0, jnp.sum(has), body, topk_init(k))
    return topk_merge(res, start.dists, start.ids)


@jax.jit
def _exact_safe_keep(dists, alive, thr_q):
    """Keep test of a full-dimension cascade stage: its survivors are the
    lanes whose whole dequantized distance is within the quantization-
    inflated START threshold, exact-safe for any tile error.  ADSampling's
    per-tile estimator is not used on these tiles: it scales a tile's
    distance by D/d, and quantization error is uneven across dims (int4 at
    D = 1536 pruned true neighbours on the first tile)."""
    return alive & (dists <= thr_q)


def _finish_partitions(alive, C: int) -> int:
    """Partitions ``_cascade_finish`` re-scores for one query's survivor
    bitmap (a host sync, taken only for the meters)."""
    return int(np.asarray(alive).reshape(-1, C).any(axis=1).sum())


@register_executor("cascade-scan")
def _exec_cascade_scan(store, pruner, Q, spec, *, ivf, mesh, stats):
    """Multi-resolution cascade: each ``spec.cascade`` stage scans a
    narrower-then-wider sequence of device mirrors over the survivors of
    the previous stage, ending in the exact f32 re-rank.

    A ``"projN[:dtype]"`` first stage scans a rank-N PCA projection mirror
    with the exact-safe lower-bound keep test (single d-tile, so the test
    fires once at full projected dimensionality — safe for ANY pruner);
    full-dimension dtype stages run the ADSampling keep test when the
    engine pruner is ADSampling and unpruned (thr = inf) otherwise, like
    fused-scan.  The threshold comes from an exact f32 START scan of the
    IVF-routed nearest bucket's first partition (partition 0 without an
    index), which is masked out of every stage and merged exactly."""
    if spec.metric != "l2":
        raise ValueError("cascade-scan is L2-only (spec validation enforces "
                         "this)")
    if spec.cascade is None:
        raise ValueError("cascade-scan executor needs spec.cascade")
    scan_stages = [parse_cascade_stage(s) for s in spec.cascade][:-1]
    mirrors = [
        projection_mirror(store, rank, dt) if kind == "proj"
        else device_mirror(store, dt)
        for kind, dt, rank in scan_stages
    ]
    use_pallas = _resolve_pallas(spec)
    P, C, D = store.num_partitions, store.capacity, store.dim
    prune = pruner.name == "adsampling" and pruner.aux is not None
    eps0 = float(pruner.aux["eps0"]) if prune else 2.1
    qerrs = [_quant_err_norm(m) for m in mirrors]
    counts = np.asarray(store.counts)
    meter = stats is not None or _metrics.enabled()
    out_i, out_d = [], []
    for q in Q:
        qt = pruner.transform_query(jnp.asarray(q, jnp.float32))
        p0 = 0
        if ivf is not None:
            order, _ = ivf.route(qt, 1, "l2", dtype=spec.route_dtype)
            if len(order):
                p0 = int(order[0])
        start = topk_from_batch(
            pdx_distance(store.data[p0], qt, "l2"), store.ids[p0], spec.k
        )
        thr = topk_threshold(start)
        ids_scan = store.ids.at[p0].set(-1)
        dists = alive = None
        lanes_in = float(counts.sum() - counts[p0])
        computed = float(counts[p0]) * D  # START (re-rank added below)
        for si, ((kind, dt, rank), mirror) in enumerate(
            zip(scan_stages, mirrors)
        ):
            # exact-safe quantization slack: anything within thr of the
            # query sits within (sqrt(thr) + qerr)^2 in dequantized space
            thr_q = (jnp.sqrt(thr) + qerrs[si]) ** 2
            if kind == "proj":
                # single d-tile covering the whole projection: the keep
                # test fires once at d = rank, where orthonormal-projection
                # L2 lower-bounds the full L2 exactly (eps 0 — intermediate
                # ADSampling-style scaled tests are unsafe on PCA-projected
                # coordinates)
                qs = jnp.matmul(qt, mirror.components,
                                precision=jax.lax.Precision.HIGHEST)
                thr_i, eps_i, d_tile = thr_q, 0.0, rank
            else:
                # full-dimension stages scan unpruned and keep what the
                # exact-safe bound on the full distance keeps (see
                # _exact_safe_keep)
                qs = qt
                thr_i, eps_i, d_tile = jnp.float32(np.inf), eps0, 64
            sc = mirror.scale if mirror.quantized else None
            off = mirror.offset if mirror.quantized else None
            dists, alive, streamed = _cascade_stage(
                mirror.data, ids_scan, alive, qs, thr_i, sc, off,
                eps_i, d_tile, use_pallas, mirror.packed, mirror.dim,
                si == 0,
            )
            if kind != "proj" and prune:
                alive = _exact_safe_keep(dists, alive, thr_q)
            if meter:
                n_surv = float(np.asarray(alive.sum()))
                # realized HBM traffic at d-tile granularity: a partition
                # fetched ``streamed`` tiles of this stage's mirror before
                # its last lane died (the first stage streams everything)
                dims_f = np.minimum(
                    np.asarray(streamed, np.float64) * d_tile,
                    float(mirror.dim),
                )
                stage_bytes = (
                    float(dims_f.sum()) * C * mirror.bytes_per_value
                )
                if stats is not None:
                    computed += lanes_in * mirror.dim
                if _metrics.enabled():
                    _metrics.counter(
                        "repro_cascade_stage_survivors", n_surv,
                        stage=str(si), stage_name=spec.cascade[si],
                    )
                    _metrics.counter(
                        "repro_cascade_stage_bytes", stage_bytes,
                        stage=str(si), stage_name=spec.cascade[si],
                    )
                    # what partition-granular skip would have streamed (an
                    # entering partition fetches its FULL stage mirror) —
                    # the realized counter above undercuts this by exactly
                    # the mid-scan d-tile savings
                    _metrics.counter(
                        "repro_cascade_stage_bytes_partition_model",
                        float((np.asarray(streamed) > 0).sum())
                        * mirror.dim * C * mirror.bytes_per_value,
                        stage=str(si), stage_name=spec.cascade[si],
                    )
                    _metrics.counter(
                        "repro_device_bytes_total", stage_bytes,
                        executor="cascade-scan", component="scan",
                        dtype=mirror.dtype,
                    )
                lanes_in = n_surv
        res = _cascade_finish(store.data, ids_scan, qt, alive, spec.k, start)
        rerank_values = (
            _finish_partitions(alive, C) * C * D if meter else 0.0
        )
        computed += rerank_values
        if stats is not None:
            total = float(counts.sum()) * D
            stats.values_total += total
            stats.values_computed += computed
            stats.values_avoided += max(total - computed, 0.0)
            stats.partitions_visited += P
        if _metrics.enabled():
            _metrics.counter(
                "repro_device_bytes_total", float(D * C * 4),
                executor="cascade-scan", component="start", dtype="f32",
            )
            _metrics.counter(
                "repro_device_bytes_total", float(rerank_values * 4),
                executor="cascade-scan", component="rerank", dtype="f32",
            )
        out_i.append(np.asarray(res.ids))
        out_d.append(np.asarray(res.dists))
    with _trace.span("rerank", fused="in-kernel"):
        pass
    return np.stack(out_i), np.stack(out_d)


# Union-survivor columns per step of a batched cascade stage: at D = 1536
# an int8 chunk is 25 MB, and the step count, not the shape, follows the
# survivor count.
_CASCADE_CHUNK = 16384


def _stage_chunk(PC: int) -> int:
    return min(_CASCADE_CHUNK, PC)


@functools.partial(
    jax.jit,
    static_argnames=("eps0", "d_tile", "use_pallas", "packed", "dim"),
)
def _cascade_batch_stage(
    mdata, idx, n_chunks, alive, qs, thr, scale, offset, eps0, d_tile,
    use_pallas, packed, dim,
):
    """One MXU-batched cascade stage: gather the union-survivor columns of
    the (P, D_i, C) stage mirror chunk by chunk into compacted (D_i, W)
    tiles, run the batched d-tile keep-test ladder over the whole query
    batch, scatter dists/alive back to flat (B, P*C) slot order (flat slot
    = p*C + c).  ``idx`` lists the union survivors first, padded with P*C
    (a throwaway column that is sliced off) to whole chunks of
    ``W = _stage_chunk(P*C)``; only the first ``n_chunks`` chunks run, a
    traced count, so every survivor count shares one executable."""
    from ..kernels.ops import batched_cascade_stage_op

    P, Dp, C = mdata.shape
    PC = P * C
    B = alive.shape[0]
    W = _stage_chunk(PC)
    flat = mdata.transpose(1, 0, 2).reshape(Dp, PC)
    alive_ext = jnp.concatenate(
        [alive, jnp.zeros((B, 1), alive.dtype)], axis=1
    )

    def chunk(c, acc):
        d_full, a_full = acc
        ic = jax.lax.dynamic_slice_in_dim(idx, c * W, W)
        d_c, a_c = batched_cascade_stage_op(
            flat[:, jnp.minimum(ic, PC - 1)], alive_ext[:, ic], qs, thr,
            scale, offset, eps0=eps0, d_tile=d_tile, use_pallas=use_pallas,
            packed=packed, dim=dim,
        )
        return d_full.at[:, ic].set(d_c), a_full.at[:, ic].set(a_c)

    d_full, a_full = jax.lax.fori_loop(0, n_chunks, chunk, (
        jnp.zeros((B, PC + 1), jnp.float32),
        jnp.zeros((B, PC + 1), jnp.bool_),
    ))
    return d_full[:, :PC], a_full[:, :PC]


@register_executor("cascade-batch")
def _exec_cascade_batch(store, pruner, Q, spec, *, ivf, mesh, stats):
    """Batch-native multi-resolution cascade: each ``spec.cascade`` stage
    runs ONCE over the whole query batch instead of once per query,
    carrying a shared (B, P*C) survivor bitmap between stages.

    Per stage, the union of every query's survivors is compacted into
    fixed-width column chunks (one compiled shape for any survivor count),
    the stage mirror's surviving columns are gathered once, and the d-tile
    ladder runs through the batched quantized MXU kernel with per-query
    thresholds — a column fetched for any query is scanned for all B, so
    stage bytes are paid per batch, not per query.  START threshold
    seeding and the exact f32 re-rank stay per query with the same
    arithmetic as ``cascade-scan``: the final top-k depends only on the
    survivor bitmap and the exact re-rank of every survivor, both of which
    this executor reproduces, so ids match the per-query path bitwise.
    The planner keeps the host loop as the B=1 fallback."""
    if spec.metric != "l2":
        raise ValueError("cascade-batch is L2-only (spec validation "
                         "enforces this)")
    if spec.cascade is None:
        raise ValueError("cascade-batch executor needs spec.cascade")
    scan_stages = [parse_cascade_stage(s) for s in spec.cascade][:-1]
    mirrors = [
        projection_mirror(store, rank, dt) if kind == "proj"
        else device_mirror(store, dt)
        for kind, dt, rank in scan_stages
    ]
    use_pallas = _resolve_pallas(spec)
    P, C, D = store.num_partitions, store.capacity, store.dim
    PC = P * C
    B = Q.shape[0]
    prune = pruner.name == "adsampling" and pruner.aux is not None
    eps0 = float(pruner.aux["eps0"]) if prune else 2.1
    qerrs = [_quant_err_norm(m) for m in mirrors]
    counts = np.asarray(store.counts)
    meter = stats is not None or _metrics.enabled()
    # START stays per query (exact arithmetic parity with cascade-scan)
    qts, starts, p0s = [], [], []
    for q in Q:
        qt = pruner.transform_query(jnp.asarray(q, jnp.float32))
        p0 = 0
        if ivf is not None:
            order, _ = ivf.route(qt, 1, "l2", dtype=spec.route_dtype)
            if len(order):
                p0 = int(order[0])
        starts.append(topk_from_batch(
            pdx_distance(store.data[p0], qt, "l2"), store.ids[p0], spec.k
        ))
        qts.append(qt)
        p0s.append(p0)
    Qt = jnp.stack(qts)                                   # (B, D)
    thr = jnp.stack([topk_threshold(s) for s in starts])  # (B,)
    p0_arr = np.asarray(p0s, np.int32)
    slot_part = jnp.arange(PC, dtype=jnp.int32) // C
    alive = (store.ids.reshape(-1)[None, :] >= 0) & (
        slot_part[None, :] != jnp.asarray(p0_arr)[:, None]
    )                                                     # (B, P*C)
    lanes_in = (counts.sum() - counts[p0_arr]).astype(np.float64)
    computed = counts[p0_arr].astype(np.float64) * D
    dists = None
    for si, ((kind, dt, rank), mirror) in enumerate(
        zip(scan_stages, mirrors)
    ):
        thr_q = (jnp.sqrt(thr) + qerrs[si]) ** 2
        if kind == "proj":
            Qs = jnp.matmul(Qt, mirror.components,
                            precision=jax.lax.Precision.HIGHEST)
            thr_i, eps_i, d_tile = thr_q, 0.0, rank
        else:  # unpruned scan, exact-safe keep below (_exact_safe_keep)
            Qs = Qt
            thr_i = jnp.full((B,), np.inf, jnp.float32)
            eps_i, d_tile = eps0, 64
        # host-synced union -> column chunks of a fixed width
        nz = np.flatnonzero(np.asarray(jnp.any(alive, axis=0)))
        width = _stage_chunk(PC)
        n_chunks = max(-(-nz.size // width), 1)
        idx_np = np.full((-(-PC // width) * width,), PC, np.int32)
        idx_np[: nz.size] = nz
        sc = mirror.scale if mirror.quantized else None
        off = mirror.offset if mirror.quantized else None
        dists, alive = _cascade_batch_stage(
            mirror.data, jnp.asarray(idx_np), n_chunks, alive, Qs, thr_i,
            sc, off, eps_i, d_tile, use_pallas, mirror.packed, mirror.dim,
        )
        if kind != "proj" and prune:
            alive = _exact_safe_keep(dists, alive, thr_q[:, None])
        S = n_chunks * width  # columns gathered, chunk padding included
        if meter:
            surv_b = np.asarray(jnp.sum(alive, axis=1)).astype(np.float64)
            # realized traffic: the compacted union columns are gathered
            # once and shared by the whole batch — the batched path's
            # bytes win over B per-query mirror walks
            stage_bytes = float(S) * mirror.dim * mirror.bytes_per_value
            if stats is not None:
                computed += lanes_in * mirror.dim
            if _metrics.enabled():
                _metrics.counter(
                    "repro_cascade_stage_survivors", float(surv_b.sum()),
                    stage=str(si), stage_name=spec.cascade[si],
                )
                _metrics.counter(
                    "repro_cascade_stage_bytes", stage_bytes,
                    stage=str(si), stage_name=spec.cascade[si],
                )
                _metrics.counter(
                    "repro_device_bytes_total", stage_bytes,
                    executor="cascade-batch", component="scan",
                    dtype=mirror.dtype,
                )
            lanes_in = surv_b
    # exact per-query finish over every lane the keep tests spared
    # (see _cascade_finish)
    out_i, out_d = [], []
    for b in range(B):
        ids_scan = store.ids.at[p0s[b]].set(-1)
        res = _cascade_finish(
            store.data, ids_scan, qts[b], alive[b], spec.k, starts[b],
        )
        rerank_values = (
            _finish_partitions(alive[b], C) * C * D if meter else 0.0
        )
        computed[b] += rerank_values
        if _metrics.enabled():
            _metrics.counter(
                "repro_device_bytes_total", float(D * C * 4),
                executor="cascade-batch", component="start", dtype="f32",
            )
            _metrics.counter(
                "repro_device_bytes_total", float(rerank_values * 4),
                executor="cascade-batch", component="rerank", dtype="f32",
            )
        out_i.append(np.asarray(res.ids))
        out_d.append(np.asarray(res.dists))
    if stats is not None:
        total = float(counts.sum()) * D
        stats.values_total += total * B
        stats.values_computed += float(computed.sum())
        stats.values_avoided += max(total * B - float(computed.sum()), 0.0)
        stats.partitions_visited += P * B
    with _trace.span("rerank", fused="in-kernel"):
        pass
    return np.stack(out_i), np.stack(out_d)


def _get_placement(store, n_shards: int, kind: str, *, ivf=None, axis="data"):
    """The store's tile->shard ``Placement``, cached per ``(tiles_version,
    n_shards, kind)`` — arranging/padding copies the tiles, which must cost
    once per sealed-tile mutation, not once per search.  A dict (not a
    single slot) so one store serving two mesh sizes, or both block and
    bucket layouts, never thrashes; stale-version entries are evicted so
    churn doesn't pin dead device arrays."""
    from ..dist.placement import Placement  # no core<->dist cycle

    version = getattr(store, "tiles_version", 0)
    key = (version, n_shards, kind)
    cache = getattr(store, "_placement_cache", None)
    if cache is None:
        cache = {}
        store._placement_cache = cache
    pl = cache.get(key)
    _metrics.counter(
        "repro_cache_events_total", cache="placement",
        event="hit" if pl is not None else "miss",
    )
    if pl is None:
        if kind == "block":
            pl = Placement.block(store.data, store.ids, n_shards, axis=axis)
        elif kind == "bucket":
            pb = getattr(store, "_part_bucket", None)
            if pb is None:  # frozen store: derive from the (synced) index
                pb = np.repeat(np.arange(ivf.nlist), ivf.part_counts)
            if len(pb) < store.num_partitions:  # all-pad placeholder tiles
                pb = np.concatenate(
                    [pb, np.full(store.num_partitions - len(pb), -1, np.int64)]
                )
            pl = Placement.bucket(
                store.data, store.ids, pb, ivf.nlist, n_shards, axis=axis
            )
        else:
            raise ValueError(f"no cached placement kind {kind!r}")
        for stale in [kk for kk in cache if kk[0] != version]:
            del cache[stale]
        cache[key] = pl
    return pl


@register_executor("block-sharded")
def _exec_block_sharded(store, pruner, Q, spec, *, ivf, mesh, stats):
    from ..dist.pdx_sharded import search_block_sharded

    pl = _get_placement(store, mesh.shape["data"], "block")
    out_i, out_d = [], []
    for q in Q:
        res = search_block_sharded(
            mesh, q=q, k=spec.k, metric=spec.metric,
            pruner=pruner, schedule=spec.schedule, delta_d=spec.delta_d,
            placement=pl, stats=stats,
        )
        out_i.append(np.asarray(res.ids))
        out_d.append(np.asarray(res.dists))
    return np.stack(out_i), np.stack(out_d)


@register_executor("dim-sharded")
def _exec_dim_sharded(store, pruner, Q, spec, *, ivf, mesh, stats):
    from ..dist.pdx_sharded import search_dim_sharded
    from ..dist.placement import Placement

    pl = Placement.replicated(store.data, store.ids, mesh.shape["model"])
    out_i, out_d = [], []
    for q in Q:
        qt = pruner.transform_query(q)
        res = search_dim_sharded(
            mesh, q=qt, k=spec.k, metric=spec.metric, placement=pl,
        )
        out_i.append(np.asarray(res.ids))
        out_d.append(np.asarray(res.dists))
    _exact_scan_stats(stats, store, len(Q))
    return np.stack(out_i), np.stack(out_d)


@register_executor("batch-block-sharded")
def _exec_batch_block_sharded(store, pruner, Q, spec, *, ivf, mesh, stats):
    from ..dist.pdx_sharded import search_batch_block_sharded

    pl = _get_placement(store, mesh.shape["data"], "block")
    Qt = _transform_batch(pruner, Q)
    dt = spec.scan_dtype
    mirror = device_mirror(store, dt) if dt != "f32" else None
    res = search_batch_block_sharded(
        mesh, Q=Qt, k=spec.k, metric=spec.metric, placement=pl,
        mirror=mirror, rerank_mult=spec.rerank_mult,
    )
    B = Q.shape[0]
    _exact_scan_stats(stats, store, B)
    if _metrics.enabled():
        from ..obs import meters as _meters

        n_sh = mesh.shape["data"]
        _meters.count_issued("batch-block-sharded", all_gather=1)
        P, D, C = store.data.shape
        bpv = mirror.bytes_per_value if mirror is not None else 4
        dtype = mirror.dtype if mirror is not None else "f32"
        wire = _meters.broadcast_batch_bytes(
            n_shards=n_sh, B=B, D=store.dim, k=spec.k
        )
        wire["scan"] = float(P * D * C * bpv)
        _meters.record_device_bytes("batch-block-sharded", dtype, wire)
    return np.asarray(res.ids), np.asarray(res.dists)


def _prepare_routed_host(store, pruner, Q, spec, *, ivf, mesh):
    """Host half of the routed executor: placement lookup, batch transform,
    bucket ranking, exchange planning, send-buffer packing.  No collective
    fires here — that's ``_run_routed_device``'s job."""
    if ivf is None:
        raise ValueError("routed_bucket executor needs an IVF index")
    if mesh is None or "data" not in getattr(mesh, "axis_names", ()):
        raise ValueError(
            "routed_bucket executor needs a mesh with a 'data' axis, got "
            f"{mesh!r}"
        )
    from ..dist.routing import prepare_routed

    pl = _get_placement(store, mesh.shape["data"], "bucket", ivf=ivf)
    Qt = _transform_batch(pruner, Q)
    sel = ivf.route_batch(Qt, spec.nprobe, spec.metric, spec.route_dtype)
    dt = spec.scan_dtype
    mirror = device_mirror(store, dt) if dt != "f32" else None
    launch = prepare_routed(
        mesh, pl, Qt, sel, spec.k, metric=spec.metric,
        mirror=mirror, rerank_mult=spec.rerank_mult,
    )
    return launch, sel


def _run_routed_device(launch, sel, store, spec, *, ivf, stats):
    """Device half: fire the prepared exchange + scan + merge collectives,
    then account the selected-bucket work."""
    from ..dist.routing import launch_routed

    res = launch_routed(launch)
    if stats is not None:
        # exact over each query's selected buckets: every live value in a
        # probed bucket is computed, everything outside is avoided by
        # routing (not by a pruning predicate — values_total counts only
        # visited partitions, matching the adaptive+IVF convention)
        counts = np.asarray(store.counts)
        po = np.asarray(ivf.part_offsets)
        pc = np.asarray(ivf.part_counts)
        bucket_rows = np.array(
            [counts[po[b]: po[b] + pc[b]].sum() for b in range(ivf.nlist)],
            dtype=np.float64,
        )
        sel_np = np.asarray(sel)
        valid = sel_np >= 0
        safe = np.where(valid, sel_np, 0)
        work = float(np.where(valid, bucket_rows[safe], 0.0).sum()) * store.dim
        stats.values_total += work
        stats.values_computed += work
        stats.partitions_visited += int(np.where(valid, pc[safe], 0).sum())
    return np.asarray(res.ids), np.asarray(res.dists)


@register_executor("routed_bucket")
def _exec_routed_bucket(store, pruner, Q, spec, *, ivf, mesh, stats):
    """Bucket-routed distributed search: queries travel to the shards that
    own their top-nprobe buckets (one all-to-all + one packed all-gather
    per batch — see ``repro.dist.routing``).  Exact over each query's
    selected buckets; with nprobe >= nlist it equals the exact full scan.

    Split into ``_prepare_routed_host`` (placement, routing plan, buffer
    packing) and ``_run_routed_device`` (collectives) so a serving loop can
    overlap batch N+1's host planning with batch N's device work — the
    blocking path here is simply the two halves back to back."""
    launch, sel = _prepare_routed_host(
        store, pruner, Q, spec, ivf=ivf, mesh=mesh
    )
    return _run_routed_device(launch, sel, store, spec, ivf=ivf, stats=stats)


# ------------------------------------------------- tiered executors
# Beyond-HBM serving: the host-RAM f32 masters stay authoritative, device
# HBM holds only a fixed slot-pool (``core.layout.BucketCache``) of the
# quantized tile extents of recently-routed IVF buckets.  A batch flows:
# route (two-level centroid tree when attached) -> ensure() admits the
# routed buckets (LRU-evicting cold ones) -> masked pool scan at
# ``spec.scan_dtype`` width -> exact re-rank against the host masters.
# ``prepare_execute`` puts routing + ensure() in the host half, so the
# serving loop's depth-1 handoff overlaps batch N+1's uploads (the
# prefetch) with batch N's device scan.

def _get_bucket_cache(store, spec, *, ivf, n_regions=1, bucket_region=None):
    """The store's ``BucketCache`` for this spec's (capacity, dtype,
    regions), cached on the store — pool allocation + quant-param passes
    must cost once per configuration, not once per batch.  Generation
    invalidation is the cache's own job (``tiles_version``)."""
    key = (spec.hbm_slots, spec.scan_dtype, int(n_regions))
    caches = getattr(store, "_tiered_cache", None)
    if caches is None:
        caches = {}
        store._tiered_cache = caches
    bc = caches.get(key)
    if bc is None:
        po = pc = None
        if getattr(store, "num_buckets", None) is None:
            po = np.asarray(ivf.part_offsets)
            pc = np.asarray(ivf.part_counts)
        bc = BucketCache(
            store, capacity_slots=spec.hbm_slots, dtype=spec.scan_dtype,
            n_regions=n_regions, bucket_region=bucket_region,
            part_offsets=po, part_counts=pc,
        )
        caches[key] = bc
    elif bucket_region is not None:
        bc._bucket_region = np.asarray(bucket_region, np.int64)
    return bc


def _tiered_scan_body(pool, pos, allowed, Qt, sc, off, rk, metric,
                      use_pallas, packed, dim):
    """Masked pool scan: every cached tile, each query restricted to the
    slots of its routed buckets — the tiered twin of ``_fused_batch_scan``
    (trace-level helper: runs standalone under jit and inside the
    routed-tiered shard_map body)."""
    from ..kernels.ops import batched_distance_quant_op
    from ..kernels.ref import dequantize_ref

    def body(state, inp):
        tile, tpos, allow_s = inp      # (D', C), (C,), (B,)
        if metric == "l1":
            t32 = dequantize_ref(tile, sc, off, packed=packed, dim=dim)
            dmat = jax.vmap(lambda q: pdx_distance(t32, q, "l1"))(Qt)
        else:
            dmat = batched_distance_quant_op(
                tile, Qt, sc, off, metric, use_pallas,
                packed=packed, dim=dim,
            )
        dmat = jnp.where(allow_s[:, None], dmat, jnp.inf)
        return jax.vmap(topk_merge, (0, 0, None))(state, dmat, tpos), None

    init = jax.vmap(lambda _: topk_init(rk))(jnp.arange(Qt.shape[0]))
    state, _ = jax.lax.scan(body, init, (pool, pos, allowed.T))
    return state


@functools.partial(
    jax.jit, static_argnames=("rk", "metric", "use_pallas", "quantized",
                              "packed", "dim")
)
def _tiered_pool_scan(
    pool, slot_ids, slot_bucket, sel, Qt, scale, offset, rk, metric,
    use_pallas, quantized, packed: bool = False, dim: int | None = None,
) -> TopK:
    """Single-host tiered scan -> per-query top-``rk`` flat POOL positions
    (s * C + c; dead/free lanes carry -1).  Positions resolve to global
    ids host-side through ``BucketCache.slot_ids_host`` — the exact
    re-rank never touches device copies of the full store."""
    S, _, C = pool.shape
    sc = scale if quantized else None
    off = offset if quantized else None
    # -1 marks BOTH unrouted sel pads (tree routing) and free pool slots;
    # remap sel pads to -2 so they can never select a free slot's tiles
    sel_safe = jnp.where(sel >= 0, sel, -2)
    allowed = (
        sel_safe[:, :, None] == slot_bucket[None, None, :]
    ).any(axis=1)                                             # (B, S)
    pos = jnp.arange(S * C, dtype=jnp.int32).reshape(S, C)
    pos = jnp.where(slot_ids >= 0, pos, -1)
    return _tiered_scan_body(
        pool, pos, allowed, Qt, sc, off, rk, metric, use_pallas, packed, dim
    )


def _host_master_rows(store) -> tuple[np.ndarray, np.ndarray]:
    """Sorted-by-id flat view of the live host-RAM f32 master rows, cached
    per ``tiles_version`` — the authoritative tier the tiered executors
    re-rank against (write-head rows merge separately and sealed tiles only
    change with tiles_version, so the sort amortizes over serving)."""
    ver = getattr(store, "tiles_version", 0)
    cached = getattr(store, "_host_rows_cache", None)
    if cached is not None and cached[0] == ver:
        return cached[1], cached[2]
    data = getattr(store, "_data", None)
    if data is not None:
        ids = store._ids
    else:
        data = np.asarray(store.data)
        ids = np.asarray(store.ids)
    flat_ids = np.asarray(ids).reshape(-1)
    live = flat_ids >= 0
    rows = np.ascontiguousarray(
        np.transpose(np.asarray(data, np.float32), (0, 2, 1))
    ).reshape(-1, data.shape[1])[live]
    flat_ids = flat_ids[live]
    order = np.argsort(flat_ids, kind="stable")
    out = (ver, flat_ids[order], rows[order])
    store._host_rows_cache = out
    return out[1], out[2]


def _tiered_rerank(
    store, cache: BucketCache, cand: TopK, Qt_np: np.ndarray, k: int,
    metric: str,
) -> tuple[np.ndarray, np.ndarray]:
    """Exact re-rank of pool-scan candidates against the HOST masters:
    positions -> cached global ids -> master rows (binary search on the
    sorted-id view) -> exact f32 metric -> top-k.  This replaces
    ``topk.rerank_positions`` for the tiered path, where gathering from a
    device-resident master copy would defeat the whole beyond-HBM point."""
    slot_ids = cache.slot_ids_host().reshape(-1)
    sorted_ids, rows = _host_master_rows(store)
    pos = np.asarray(cand.ids)
    B = pos.shape[0]
    out_i = np.full((B, k), -1, np.int64)
    out_d = np.full((B, k), np.inf, np.float32)
    for b in range(B):
        p = pos[b]
        gids = np.where(p >= 0, slot_ids[np.maximum(p, 0)], -1)
        gids = gids[gids >= 0]
        if gids.size == 0:
            continue
        loc = np.searchsorted(sorted_ids, gids)  # cached ids are all live
        x = rows[loc]
        q = Qt_np[b]
        if metric == "l2":
            d = ((x - q) ** 2).sum(axis=1)
        elif metric == "l1":
            d = np.abs(x - q).sum(axis=1)
        else:
            d = -(x @ q)
        order = np.argsort(d, kind="stable")[: k]
        out_i[b, : len(order)] = gids[order]
        out_d[b, : len(order)] = d[order].astype(np.float32)
    return out_i, out_d


def _tiered_chunks(
    sel: np.ndarray, cnts: np.ndarray, region_of, region_slots: int,
) -> list[list[int]]:
    """Greedy query chunking so each chunk's union bucket demand fits the
    pool (per region): batches whose routed set overflows the cache run as
    several ensure+scan rounds instead of failing.  A chunk is cut when
    admitting the next query's buckets would overflow any region."""
    B = sel.shape[0]
    chunks: list[list[int]] = []
    cur: list[int] = []
    seen: set[int] = set()
    demand: dict[int, int] = {}
    for b in range(B):
        row = [int(x) for x in sel[b]
               if x >= 0 and int(cnts[int(x)]) > 0]
        new = [x for x in dict.fromkeys(row) if x not in seen]
        add: dict[int, int] = {}
        for x in new:
            r = region_of(x)
            add[r] = add.get(r, 0) + int(cnts[x])
        fits = all(
            demand.get(r, 0) + a <= region_slots for r, a in add.items()
        )
        if cur and not fits:
            chunks.append(cur)
            cur, seen, demand = [], set(), {}
            new = list(dict.fromkeys(row))
            add = {}
            for x in new:
                r = region_of(x)
                add[r] = add.get(r, 0) + int(cnts[x])
        cur.append(b)
        seen.update(new)
        for r, a in add.items():
            demand[r] = demand.get(r, 0) + a
    if cur:
        chunks.append(cur)
    return chunks


def _chunk_passes(
    chunk_sel: np.ndarray, cnts: np.ndarray, region_of, region_slots: int,
) -> list[tuple[list[int], dict | None]]:
    """Pass schedule for one chunk's routed bucket union: a list of
    ``(bucket_list, parts)`` upload requests, each fitting every cache
    region.  The common case — demand fits — is one full pass.  A bucket
    whose extent alone exceeds a region is cut into region-sized
    sub-extents (``parts[b] = (part_i, n_parts)``, ceil-divided), and the
    items pack greedily into sequential passes; the run loop scans each
    pass and merges top-k, so a single query whose routed demand exceeds
    the slot pool succeeds instead of raising."""
    uniq: list[int] = []
    for row in chunk_sel:
        for x in row:
            x = int(x)
            if x >= 0 and x < len(cnts) and int(cnts[x]) > 0:
                uniq.append(x)
    uniq = list(dict.fromkeys(uniq))
    demand: dict[int, int] = {}
    for b in uniq:
        r = region_of(b)
        demand[r] = demand.get(r, 0) + int(cnts[b])
    if all(d <= region_slots for d in demand.values()):
        return [(uniq, None)]
    items: list[tuple[int, tuple | None, int]] = []
    for b in uniq:
        c = int(cnts[b])
        if c > region_slots:
            n_parts = -(-c // region_slots)
            per = -(-c // n_parts)
            for pi in range(n_parts):
                items.append((b, (pi, n_parts), min(per, c - pi * per)))
        else:
            items.append((b, None, c))
    passes: list[tuple[list[int], dict | None]] = []
    cur: list[int] = []
    parts: dict[int, tuple] = {}
    used: dict[int, int] = {}
    for b, part, size in items:
        r = region_of(b)
        if cur and used.get(r, 0) + size > region_slots:
            passes.append((cur, parts or None))
            cur, parts, used = [], {}, {}
        cur.append(b)
        if part is not None:
            parts[b] = part
        used[r] = used.get(r, 0) + size
    if cur:
        passes.append((cur, parts or None))
    return passes


def _merge_topk_rows(
    i1: np.ndarray, d1: np.ndarray, i2: np.ndarray, d2: np.ndarray, k: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Row-wise top-k merge of two (B, k) id/dist result blocks with id
    dedup — later passes of a split chunk rescan still-resident buckets
    (and leftover sub-extents), so the same vector can surface twice; the
    exact re-rank makes duplicate distances identical, keep one."""
    B = i1.shape[0]
    out_i = np.full((B, k), -1, np.int64)
    out_d = np.full((B, k), np.inf, np.float32)
    for b in range(B):
        ids = np.concatenate([i1[b], i2[b]])
        ds = np.concatenate([d1[b], d2[b]]).astype(np.float32)
        live = ids >= 0
        ids, ds = ids[live], ds[live]
        if ids.size == 0:
            continue
        order = np.lexsort((ds, ids))
        ids, ds = ids[order], ds[order]
        keep = np.ones(ids.size, bool)
        keep[1:] = ids[1:] != ids[:-1]
        ids, ds = ids[keep], ds[keep]
        order = np.argsort(ds, kind="stable")[:k]
        out_i[b, : order.size] = ids[order]
        out_d[b, : order.size] = ds[order]
    return out_i, out_d


@dataclasses.dataclass
class _TieredLaunch:
    """Host-side product of ``_prepare_tiered_host``: the routed set, the
    chunk schedule with each chunk's pass schedule, and the FIRST pass's
    in-flight upload ticket — ``issue``-ing it at prepare time is the
    prefetch (the H2D copies overlap the previous batch's device scan
    through the serving handoff, and ``run`` only pays the residual
    ``wait``).  Later passes issue inside ``run``, one ahead of the scan;
    functional pool updates keep every captured snapshot consistent."""

    cache: BucketCache
    Qt: jax.Array
    Qt_np: np.ndarray
    sel: np.ndarray
    chunks: list
    passes: list
    ticket: object
    rk: int
    use_pallas: bool


def _tiered_rk(spec: SearchSpec, cache: BucketCache, C: int) -> int:
    if spec.scan_dtype == "f32":
        return spec.k
    return min(spec.rerank_mult * spec.k, cache.capacity_slots * C)


def _prepare_tiered_host(store, pruner, Q, spec, *, ivf) -> _TieredLaunch:
    """Host half of the tiered executor: batch transform, bucket routing,
    chunk planning, and the first chunk's ``ensure`` (the prefetch)."""
    if ivf is None:
        raise ValueError(
            "tiered-scan executor needs an IVF index (spec.hbm_slots caches "
            "at bucket granularity, which only routing defines)"
        )
    cache = _get_bucket_cache(store, spec, ivf=ivf)
    Qt = _transform_batch(pruner, jnp.asarray(Q, jnp.float32))
    with _trace.span("route", nprobe=spec.nprobe, tiered=True):
        sel = np.asarray(
            ivf.route_batch(Qt, spec.nprobe, spec.metric, spec.route_dtype)
        )
    _, cnts = cache._bucket_extent()
    chunks = _tiered_chunks(sel, cnts, cache._region_of, cache.region_slots)
    passes = [
        _chunk_passes(sel[chunk], cnts, cache._region_of, cache.region_slots)
        for chunk in chunks
    ]
    blist, parts = passes[0][0]
    with _trace.span("prefetch", buckets=len(blist)):
        ticket = cache.issue(np.asarray(blist, np.int64), parts=parts)
    C = store.capacity
    return _TieredLaunch(
        cache=cache, Qt=Qt, Qt_np=np.asarray(Qt), sel=sel, chunks=chunks,
        passes=passes, ticket=ticket,
        rk=_tiered_rk(spec, cache, C), use_pallas=_resolve_pallas(spec),
    )


def _tiered_stats(stats, store, cache, sel, ivf) -> None:
    """Selected-bucket work accounting, matching the routed convention:
    every live value in a probed bucket is computed, everything outside is
    avoided by routing."""
    if stats is None:
        return
    counts = np.asarray(store.counts)
    offs, cnts = cache._bucket_extent()
    nb = len(cnts)
    bucket_rows = np.array(
        [counts[offs[b]: offs[b] + cnts[b]].sum() for b in range(nb)],
        dtype=np.float64,
    )
    valid = sel >= 0
    safe = np.where(valid, sel, 0)
    work = float(np.where(valid, bucket_rows[safe], 0.0).sum()) * store.dim
    stats.values_total += work
    stats.values_computed += work
    stats.partitions_visited += int(np.where(valid, cnts[safe], 0).sum())


def _tiered_steps(launch: _TieredLaunch) -> list[tuple[int, int]]:
    """Flattened (chunk, pass) schedule of a tiered launch."""
    return [
        (ci, pi)
        for ci in range(len(launch.chunks))
        for pi in range(len(launch.passes[ci]))
    ]


def _tiered_step_ready(cache, launch, ticket, ci, pi):
    """Settle the step's prefetch ticket and hand back a consistent scan
    snapshot.  The ticket normally covers exactly this pass; when a
    concurrent batch's ``issue`` stole slots in between (the serving loop
    prepares N+1 while N runs), re-admit synchronously — correctness never
    rides on the overlap."""
    cache.wait(ticket)
    blist, parts = launch.passes[ci][pi]
    if not cache.resident_ok(np.asarray(blist, np.int64), parts=parts):
        cache.ensure(np.asarray(blist, np.int64), parts=parts)
    return cache.snapshot()


def _tiered_step_issue_next(cache, launch, steps, si):
    """Start the NEXT step's uploads (host quantize + async H2D) while the
    step just dispatched is still scanning on device."""
    if si + 1 >= len(steps):
        return None
    nci, npi = steps[si + 1]
    blist, parts = launch.passes[nci][npi]
    return cache.issue(np.asarray(blist, np.int64), parts=parts)


def _run_tiered_device(launch: _TieredLaunch, store, spec, *, ivf, stats):
    """Device half: per (chunk, pass) step, settle the step's prefetch
    ticket -> masked pool scan -> issue the NEXT step's uploads under the
    scan -> exact host re-rank; multi-pass chunks (routed demand beyond
    the slot pool) merge their per-pass top-k, chunk results concatenate
    back into batch order."""
    cache, sel = launch.cache, launch.sel
    B = sel.shape[0]
    out_i = np.full((B, spec.k), -1, np.int64)
    out_d = np.full((B, spec.k), np.inf, np.float32)
    C = store.capacity
    steps = _tiered_steps(launch)
    ticket = launch.ticket
    for si, (ci, pi) in enumerate(steps):
        chunk = launch.chunks[ci]
        arrays, slot_ids = _tiered_step_ready(cache, launch, ticket, ci, pi)
        pool, ids_dev, slot_bucket, scale, offset = arrays
        sel_dev = jnp.asarray(sel[chunk], jnp.int32)
        cand = _tiered_pool_scan(
            pool, ids_dev, slot_bucket, sel_dev, launch.Qt[jnp.asarray(chunk)],
            scale, offset, launch.rk, spec.metric, launch.use_pallas,
            cache.quantized, packed=cache.packed, dim=cache.dim,
        )
        # the scan is in flight: overlap the next step's staging + copy
        ticket = _tiered_step_issue_next(cache, launch, steps, si)
        ids_c, dists_c = _tiered_rerank(
            store, _TieredSnapshot(slot_ids), cand, launch.Qt_np[chunk],
            spec.k, spec.metric,
        )
        if pi == 0:
            out_i[chunk] = ids_c
            out_d[chunk] = dists_c
        else:
            out_i[chunk], out_d[chunk] = _merge_topk_rows(
                out_i[chunk], out_d[chunk], ids_c, dists_c, spec.k
            )
        if _metrics.enabled():
            S = cache.capacity_slots
            _metrics.counter(
                "repro_device_bytes_total",
                float(S) * cache.dim * C * cache.bytes_per_value,
                executor="tiered-scan", component="scan", dtype=cache.dtype,
            )
    cache.wait(ticket)
    _tiered_stats(stats, store, cache, sel, ivf)
    return out_i, out_d


class _TieredSnapshot:
    """Adapter handing ``_tiered_rerank`` a frozen ``slot_ids_host`` copy
    (a later chunk's ensure() must not remap an earlier chunk's candidate
    positions mid-resolution)."""

    def __init__(self, slot_ids: np.ndarray):
        self._slot_ids = np.array(slot_ids, copy=True)

    def slot_ids_host(self) -> np.ndarray:
        return self._slot_ids


@register_executor("tiered-scan")
def _exec_tiered_scan(store, pruner, Q, spec, *, ivf, mesh, stats):
    """Tiered beyond-HBM search: route -> ensure (bucket-granular LRU HBM
    cache) -> masked quantized pool scan -> exact host-RAM re-rank.  The
    blocking composition of ``_prepare_tiered_host`` + ``_run_tiered_device``
    (the serving loop overlaps the two halves across batches)."""
    launch = _prepare_tiered_host(store, pruner, Q, spec, ivf=ivf)
    return _run_tiered_device(launch, store, spec, ivf=ivf, stats=stats)


# ------------------------------------------------- routed tiered (mesh)
_TIERED_SHARD_CACHE: dict = {}


def _tiered_shard_exec(mesh, axis: str, rk: int, metric: str,
                       quantized: bool, packed: bool, dim: int | None,
                       use_pallas: bool):
    """Cached jitted shard_map executor for the routed-tiered scan: the
    slot pool is region-split over the mesh 'data' axis (region r == shard
    r's slice), queries + routed sets replicate, each shard scans only its
    region's cached tiles (masked to each query's routed buckets), and the
    per-shard top-``rk`` GLOBAL pool positions cross the mesh in ONE packed
    all-gather — candidate resolution + the exact re-rank stay host-side
    against the RAM masters."""
    key = (mesh, axis, rk, metric, quantized, packed, dim, use_pallas)
    fn = _TIERED_SHARD_CACHE.get(key)
    if fn is not None:
        _metrics.counter(
            "repro_cache_events_total", cache="tiered-shard", event="hit"
        )
        return fn
    _metrics.counter(
        "repro_cache_events_total", cache="tiered-shard", event="miss"
    )
    n_sh = mesh.shape[axis]

    def local(pool_sh, pos_sh, sb_sh, sel_rep, Qt_rep, scale, offset):
        sc = scale if quantized else None
        off = offset if quantized else None
        sel_safe = jnp.where(sel_rep >= 0, sel_rep, -2)
        allowed = (
            sel_safe[:, :, None] == sb_sh[None, None, :]
        ).any(axis=1)                                      # (B, S_r)
        cand = _tiered_scan_body(
            pool_sh, pos_sh, allowed, Qt_rep, sc, off, rk, metric,
            use_pallas, packed, dim,
        )
        B = Qt_rep.shape[0]
        packed_buf = jnp.concatenate(
            [cand.dists,
             jax.lax.bitcast_convert_type(cand.ids, jnp.float32)],
            axis=1,
        )                                                  # (B, 2rk)
        allp = jax.lax.all_gather(packed_buf, axis, axis=1, tiled=True)
        allp = allp.reshape(B, n_sh, 2 * rk)
        all_d = allp[:, :, :rk].reshape(B, n_sh * rk)
        all_p = jax.lax.bitcast_convert_type(
            allp[:, :, rk:], jnp.int32
        ).reshape(B, n_sh * rk)
        merge = lambda dd, ii: topk_merge(topk_init(rk), dd, ii)  # noqa: E731
        return jax.vmap(merge)(all_d, all_p)

    def wrapper(pool, ids_dev, slot_bucket, sel, Qt, scale, offset):
        S, _, C = pool.shape
        pos = jnp.arange(S * C, dtype=jnp.int32).reshape(S, C)
        pos = jnp.where(ids_dev >= 0, pos, -1)
        from jax.sharding import PartitionSpec as P

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P(), P(), P()),
            out_specs=TopK(dists=P(), ids=P()),
            check_vma=False,
        )(pool, pos, slot_bucket, sel, Qt, scale, offset)

    fn = jax.jit(wrapper)
    _TIERED_SHARD_CACHE[key] = fn
    return fn


def _prepare_routed_tiered_host(store, pruner, Q, spec, *, ivf, mesh):
    """Host half of routed-tiered: region assignment (bucket -> owner shard,
    the same greedy LPT balance bucket placements use), routing, chunk
    planning, first-chunk prefetch."""
    if ivf is None:
        raise ValueError("routed_tiered executor needs an IVF index")
    if mesh is None or "data" not in getattr(mesh, "axis_names", ()):
        raise ValueError(
            "routed_tiered executor needs a mesh with a 'data' axis, got "
            f"{mesh!r}"
        )
    from ..dist.placement import assign_buckets

    n_sh = mesh.shape["data"]
    # derive the region split from the CURRENT bucket extents (deterministic
    # across hosts); the cache regenerates whole-pool on tiles_version bumps,
    # so a refreshed assignment can never mix with stale residency
    tmp = _get_bucket_cache(store, spec, ivf=ivf, n_regions=n_sh)
    _, cnts = tmp._bucket_extent()
    region = assign_buckets(cnts, n_sh)
    cache = _get_bucket_cache(
        store, spec, ivf=ivf, n_regions=n_sh, bucket_region=region
    )
    Qt = _transform_batch(pruner, jnp.asarray(Q, jnp.float32))
    with _trace.span("route", nprobe=spec.nprobe, tiered=True,
                     n_shards=n_sh):
        sel = np.asarray(
            ivf.route_batch(Qt, spec.nprobe, spec.metric, spec.route_dtype)
        )
    chunks = _tiered_chunks(sel, cnts, cache._region_of, cache.region_slots)
    passes = [
        _chunk_passes(sel[chunk], cnts, cache._region_of, cache.region_slots)
        for chunk in chunks
    ]
    blist, parts = passes[0][0]
    with _trace.span("prefetch", buckets=len(blist)):
        ticket = cache.issue(np.asarray(blist, np.int64), parts=parts)
    return _TieredLaunch(
        cache=cache, Qt=Qt, Qt_np=np.asarray(Qt), sel=sel, chunks=chunks,
        passes=passes, ticket=ticket,
        rk=_tiered_rk(spec, cache, store.capacity),
        use_pallas=_resolve_pallas(spec),
    )


def _run_routed_tiered_device(launch: _TieredLaunch, store, spec, *, ivf,
                              mesh, stats):
    cache, sel = launch.cache, launch.sel
    B = sel.shape[0]
    out_i = np.full((B, spec.k), -1, np.int64)
    out_d = np.full((B, spec.k), np.inf, np.float32)
    fn = _tiered_shard_exec(
        mesh, "data", launch.rk, spec.metric, cache.quantized,
        cache.packed, cache.dim, launch.use_pallas,
    )
    C = store.capacity
    steps = _tiered_steps(launch)
    ticket = launch.ticket
    for si, (ci, pi) in enumerate(steps):
        chunk = launch.chunks[ci]
        arrays, slot_ids = _tiered_step_ready(cache, launch, ticket, ci, pi)
        pool, ids_dev, slot_bucket, scale, offset = arrays
        sel_dev = jnp.asarray(sel[chunk], jnp.int32)
        cand = fn(
            pool, ids_dev, slot_bucket, sel_dev,
            launch.Qt[jnp.asarray(chunk)], scale, offset,
        )
        ticket = _tiered_step_issue_next(cache, launch, steps, si)
        ids_c, dists_c = _tiered_rerank(
            store, _TieredSnapshot(slot_ids), cand, launch.Qt_np[chunk],
            spec.k, spec.metric,
        )
        if pi == 0:
            out_i[chunk] = ids_c
            out_d[chunk] = dists_c
        else:
            out_i[chunk], out_d[chunk] = _merge_topk_rows(
                out_i[chunk], out_d[chunk], ids_c, dists_c, spec.k
            )
        if _metrics.enabled():
            from ..obs import meters as _meters

            _meters.count_issued("routed_tiered", all_gather=1)
            n_sh = mesh.shape["data"]
            _meters.record_device_bytes("routed_tiered", cache.dtype, {
                "scan": float(cache.capacity_slots) * cache.dim * C
                        * cache.bytes_per_value,
                "all_gather": float(n_sh * len(chunk) * 2 * launch.rk * 4),
            })
    cache.wait(ticket)
    _tiered_stats(stats, store, cache, sel, ivf)
    return out_i, out_d


@register_executor("routed_tiered")
def _exec_routed_tiered(store, pruner, Q, spec, *, ivf, mesh, stats):
    """Distributed tiered search: each mesh shard caches one region of the
    bucket pool (regions follow the same greedy bucket->shard balance as
    bucket placements), scans only its region's routed tiles, and the
    global candidate merge crosses the mesh in ONE packed all-gather per
    chunk; id resolution + exact f32 re-rank stay on the host masters."""
    launch = _prepare_routed_tiered_host(
        store, pruner, Q, spec, ivf=ivf, mesh=mesh
    )
    return _run_routed_tiered_device(
        launch, store, spec, ivf=ivf, mesh=mesh, stats=stats
    )
