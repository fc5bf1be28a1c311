"""Declarative search specification — *what* to search, not *how*.

The paper's point is that one layout (PDX) serves many search strategies:
exact scans, ADSampling/BSA/BOND dimension pruning, IVF routing, batched
MXU scans, and the sharded distributed paths.  A ``SearchSpec`` captures the
strategy-level knobs once; the planner (``repro.core.plan``) maps a
``(spec, store, query shape, optional mesh)`` onto the right execution mode,
so callers never hand-pick ``search`` vs ``search_jit`` vs ``search_batch``
vs the ``repro.dist`` entry points again.

    spec = SearchSpec(k=10, nprobe=16)
    res = engine.search(q, spec)          # single query
    res = engine.search(Q, spec)          # (B, D) batch — planner batches
    res.ids, res.dists, res.plan          # plan records executor + reason

Specs are frozen (hashable, reusable across queries and engines) and
validated at construction.  The pruning *algorithm* (ADSampling's rotation,
BSA's PCA, BOND's means) is build-time engine state — it transforms the
stored vectors — so the spec carries its runtime configuration (boundary
schedule, selectivity threshold, grouping) and the planner records the
engine pruner's stable fingerprint in the plan trace.

Specs are also store-agnostic: the same spec searches a frozen ``PDXStore``
and a live ``MutablePDXStore`` under churn.  The mutable store's monotone
``version`` is not spec state — it rides in the ``ExecutionPlan`` trace
(``plan.store_version``) and in the jitted-executor cache keys, so a spec
reused across mutations always executes against the tiles it claims to.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .distance import METRICS
from .layout import SCAN_DTYPES
from .pdxearch import SearchStats

__all__ = ["SearchSpec", "SearchResult", "parse_cascade_stage"]

SCHEDULES = ("adaptive", "fixed")
ROUTINGS = ("broadcast", "bucket")
KERNELS = ("auto", "pallas", "jnp")
# Full-dimension dtypes a cascade may run between the (optional) projection
# stage and the mandatory exact "f32" re-rank terminator.
CASCADE_MID_DTYPES = ("bf16", "int8", "int4")


def parse_cascade_stage(stage: str) -> tuple[str, str, int]:
    """One cascade stage string -> (kind, dtype, rank).

    Stage grammar:
      "projN"         — rank-N learned-projection scan, f32 mirror
      "projN:dtype"   — rank-N projection scan at a quantized mirror dtype
      "bf16"|"int8"|"int4" — full-dimension scan at that mirror dtype
      "f32"           — the exact full-precision re-rank (always last)

    Returns ``kind`` in ("proj", "scan", "exact"); ``rank`` is 0 except for
    projection stages.  Raises ValueError on anything else.
    """
    if stage == "f32":
        return ("exact", "f32", 0)
    if stage in CASCADE_MID_DTYPES:
        return ("scan", stage, 0)
    if stage.startswith("proj"):
        body = stage[4:]
        rank_s, _, dt = body.partition(":")
        dt = dt or "f32"
        if rank_s.isdigit() and int(rank_s) >= 1 and dt in SCAN_DTYPES:
            return ("proj", dt, int(rank_s))
    raise ValueError(
        f"bad cascade stage {stage!r}: expected 'projN[:dtype]', one of "
        f"{CASCADE_MID_DTYPES}, or the final 'f32'"
    )


@dataclasses.dataclass(frozen=True)
class SearchSpec:
    """Declarative description of one vector-similarity search.

    Result shaping
      k          — neighbours to return per query.
      metric     — "l2" | "l1" | "ip" (all minimized; ip is negated).

    Pruning configuration (PDXearch phases; see ``core.pdxearch``)
      schedule   — boundary schedule: "adaptive" (exponential steps, the
                   paper's fix for fixed-Δd tail latency) or "fixed".
      delta_d    — step size for the "fixed" schedule.
      sel_frac   — surviving fraction below which the PRUNE phase compacts
                   survivors (paper: 0.2).
      group      — partitions evaluated per pruning round (host path).

    IVF routing
      nprobe     — buckets probed when the engine has an IVF index.
      routing    — distributed query routing on a "data"-axis mesh:
                   "bucket" (default) routes each query only to the shards
                   owning its top-nprobe buckets (one all-to-all per batch,
                   bucket-owned placement); "broadcast" keeps the IVF
                   routing host-side (the pre-placement behavior).  Without
                   a mesh or an IVF index the knob is inert.

    Device-scan precision (the bandwidth lever; see ``core.layout``'s
    dtype-policy block)
      scan_dtype  — operand precision of the device scan: "f32" streams the
                    master tiles; "bf16"/"int8" stream the quantized device
                    mirror (2x/4x fewer bytes per dimension value) and the
                    executor re-ranks the top ``rerank_mult * k`` candidates
                    against the f32 masters, so *returned distances stay
                    exact*.  On a mesh the batched/routed sharded
                    executors scan their mirror slices the same way (the
                    per-query block-/dim-sharded paths scan f32 masters
                    and record that in the plan reason); queries and
                    candidate distances stay f32 on the wire (rounding
                    either breaks exact k-boundary ordering — see
                    ``repro.dist.routing``).
      kernel      — scan implementation: "pallas" forces the fused Pallas
                    executors (``repro.kernels``; interpreted on the CPU),
                    "jnp" forces the XLA-fused jnp bodies, "auto" picks
                    pallas on a TPU backend and jnp elsewhere.
      rerank_mult — exact-re-rank candidate multiplier (top ``rerank_mult *
                    k`` approximate candidates are re-scored in f32 when
                    ``scan_dtype != "f32"``).
      cascade     — multi-resolution scan pipeline, e.g.
                    ``("proj32:int4", "int8", "f32")``: an optional skinny
                    learned-projection stage first (``"projN[:dtype]"`` —
                    rank-N PCA mirror, exact-safe lower-bound keep test),
                    then full-dimension scans at decreasing-width mirror
                    dtypes over the survivors of the previous stage, ending
                    in the mandatory exact ``"f32"`` re-rank.  Each stage
                    seeds its keep-mask from the previous stage's alive
                    bitmap, so later (wider) stages only touch survivors;
                    the Pallas path skips pruned partitions' HBM traffic
                    entirely (prefetch-skip).  None (default) = the
                    single-level ``scan_dtype`` behavior.  L2 only.
      route_dtype — precision of the IVF centroid routing scan ("f32"
                    default; "int8"/"int4" stream a quantized centroid
                    mirror so routing bytes shrink with the same dtype
                    policy as the data scan).  Near-tie bucket *order* may
                    differ from f32 routing at partial nprobe.
      hbm_slots   — tiered serving: cap the device-resident working set at
                    this many tile slots and manage them as a bucket-
                    granular LRU cache (``core.layout.BucketCache``) fed by
                    IVF routing, instead of mirroring the whole store in
                    HBM.  Requires an IVF index; ``scan_dtype`` picks the
                    cached tiles' precision and the exact f32 re-rank runs
                    against the host-RAM masters.  None (default) keeps the
                    fully-resident mirror behavior.

    Execution hints (planner inputs, never change *results* beyond the
    pruner's own approximation)
      executor          — force a registered executor by name (see
                          ``repro.core.plan.executor_names()``); None lets
                          the planner choose.
      prefer_static     — prefer the shape-static masked path over the
                          host-orchestrated adaptive one (for callers that
                          need the whole search inside one jit).
      batch_collectives — on a mesh, amortize the top-k merge collective
                          over the whole query batch (one all-gather per
                          batch) instead of issuing it per query.
    """

    k: int = 10
    metric: str = "l2"
    schedule: str = "adaptive"
    delta_d: int = 32
    sel_frac: float = 0.2
    group: int = 8
    nprobe: int = 8
    executor: Optional[str] = None
    prefer_static: bool = False
    batch_collectives: bool = True
    routing: str = "bucket"
    scan_dtype: str = "f32"
    kernel: str = "auto"
    rerank_mult: int = 4
    cascade: Optional[tuple] = None
    route_dtype: str = "f32"
    hbm_slots: Optional[int] = None

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.metric not in METRICS:
            raise ValueError(f"metric must be one of {METRICS}, got {self.metric!r}")
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {self.schedule!r}"
            )
        if self.delta_d < 1:
            raise ValueError(f"delta_d must be >= 1, got {self.delta_d}")
        if not (0.0 < self.sel_frac <= 1.0):
            raise ValueError(f"sel_frac must be in (0, 1], got {self.sel_frac}")
        if self.group < 1:
            raise ValueError(f"group must be >= 1, got {self.group}")
        if self.nprobe < 1:
            raise ValueError(f"nprobe must be >= 1, got {self.nprobe}")
        if self.routing not in ROUTINGS:
            raise ValueError(
                f"routing must be one of {ROUTINGS}, got {self.routing!r}"
            )
        if self.scan_dtype not in SCAN_DTYPES:
            raise ValueError(
                f"scan_dtype must be one of {SCAN_DTYPES}, "
                f"got {self.scan_dtype!r}"
            )
        if self.kernel not in KERNELS:
            raise ValueError(
                f"kernel must be one of {KERNELS}, got {self.kernel!r}"
            )
        if self.rerank_mult < 1:
            raise ValueError(
                f"rerank_mult must be >= 1, got {self.rerank_mult}"
            )
        if self.route_dtype not in SCAN_DTYPES:
            raise ValueError(
                f"route_dtype must be one of {SCAN_DTYPES}, "
                f"got {self.route_dtype!r}"
            )
        if self.hbm_slots is not None and self.hbm_slots < 1:
            raise ValueError(
                f"hbm_slots must be >= 1 when set, got {self.hbm_slots}"
            )
        if self.cascade is not None:
            stages = self.cascade
            if not (
                isinstance(stages, tuple)
                and len(stages) >= 2
                and all(isinstance(s, str) for s in stages)
            ):
                raise ValueError(
                    f"cascade must be a tuple of >= 2 stage strings, "
                    f"got {stages!r}"
                )
            if self.metric != "l2":
                raise ValueError(
                    "cascade scans are L2-only (the projection lower bound "
                    f"and the ADSampling test both assume it), got metric="
                    f"{self.metric!r}"
                )
            parsed = [parse_cascade_stage(s) for s in stages]  # may raise
            if parsed[-1][0] != "exact":
                raise ValueError(
                    f"cascade must end with the exact 'f32' re-rank, "
                    f"got {stages!r}"
                )
            for pos, (kind, _, _) in enumerate(parsed):
                if kind == "proj" and pos != 0:
                    raise ValueError(
                        f"a projection stage must come first, got {stages!r}"
                    )
                if kind == "exact" and pos != len(parsed) - 1:
                    raise ValueError(
                        f"'f32' is the terminal re-rank stage, got {stages!r}"
                    )
            if len(set(stages)) != len(stages):
                raise ValueError(f"duplicate cascade stages in {stages!r}")

    def replace(self, **changes) -> "SearchSpec":
        """A copy with ``changes`` applied (specs are immutable)."""
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class SearchResult:
    """Search output plus its provenance.

    ``ids``/``dists`` are (k,) for a single query, (B, k) for a batch.
    ``plan`` is the ``repro.core.plan.ExecutionPlan`` the planner chose
    (executor name + reason + the store version searched), ``stats`` the
    work accounting when requested, and ``trace`` the per-query span record
    (``repro.obs.trace.QueryTrace``) when observability is enabled.

    Unpacks like the legacy ``(ids, dists)`` tuple::

        ids, dists = engine.search(q, spec)
    """

    ids: np.ndarray
    dists: np.ndarray
    spec: SearchSpec
    plan: "ExecutionPlan"  # noqa: F821 — repro.core.plan (no import cycle)
    stats: Optional[SearchStats] = None
    trace: Optional["QueryTrace"] = None  # noqa: F821 — repro.obs.trace

    def __iter__(self):
        yield self.ids
        yield self.dists

    def __getitem__(self, i):
        return (self.ids, self.dists)[i]

    def __len__(self) -> int:
        return 2
