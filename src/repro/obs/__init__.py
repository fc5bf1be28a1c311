"""repro.obs — runtime telemetry for the whole search stack.

Four cooperating pieces, all behind one process-wide enable flag
(``repro.obs.metrics.set_enabled`` / the ``REPRO_OBS=1`` environment
variable).  Disabled is the default and costs one boolean check per
instrumentation site: no registry mutation, no span objects, no profiler
annotation, no sampler thread, no extra device synchronization.

``obs.metrics``
    A process-wide, thread-safe ``MetricsRegistry`` of labeled counters,
    gauges, and log2-bucketed histograms with a deterministic
    ``snapshot()``, JSON dump, and Prometheus-style text exposition.

``obs.trace``
    A span tracer producing per-query ``QueryTrace`` records, kept in a
    bounded ring buffer and exportable as Chrome/Perfetto trace JSON
    (``chrome://tracing`` / https://ui.perfetto.dev).

``obs.host``
    The host-runtime witnesses: a sampler thread, running while telemetry
    is on, that counts the process's stalls (late wake-ups), and the
    garbage collector's time from ``gc.callbacks``.

``obs.meters``
    Bytes-moved and collective accounting: the demand-bytes model of the
    fused keep-mask scan, the routed/broadcast wire-byte models (the single
    source of truth the benchmarks consume), and the jaxpr-walking
    ``collective_counts`` meter recorded per executor at compile time.
    Imported on demand (``from repro.obs import meters``): it pulls in the
    kernel oracles, which the always-imported registry/tracer must not.

Metric naming scheme
--------------------
Every metric is ``repro_<subsystem>_<noun>[_<unit>]`` with counters
suffixed ``_total``; label keys are lowercase identifiers.  The registered
families:

    repro_search_batches_total{executor}        search() calls per executor
    repro_search_queries_total{executor}        queries per executor
    repro_search_latency_seconds{executor}      per-batch wall time (histogram)
    repro_pruning_values_total{executor,kind}   kind=total|computed|avoided —
                                                the SearchStats work account,
                                                mirrored into the registry
    repro_cache_events_total{cache,event}       cache=exec|placement|routed|
                                                mirror, event=hit|miss
    repro_store_mutations_total{op}             op=insert|delete|flush|repack
    repro_store_rows_mutated_total{op}          rows touched per op
    repro_store_live_vectors                    gauge
    repro_store_head_fill                       gauge, write-head occupancy 0..1
    repro_store_meta_staleness                  gauge, mutations since last
                                                dim_means/dim_vars refresh
                                                over live rows
    repro_store_device_uploads_total            full sealed-tile re-uploads
    repro_mirror_builds_total{dtype}            mirror (re)quantize events
    repro_routing_demand                        histogram of per-batch max
                                                (src, dst) demand — the log2
                                                buckets ARE the demand octaves
    repro_routing_spill_rounds_total{rounds}    rounds=1|2 exchange rounds
    repro_routing_slot_occupancy                gauge, real / padded send slots
    repro_collectives_issued_total{executor,primitive}
                                                collectives issued at runtime,
                                                derived from the executed plan
    repro_collectives_per_call{executor,primitive}
                                                gauge, counted in the jaxpr at
                                                compile time (obs.meters)
    repro_device_bytes_total{executor,component,dtype}
                                                component=scan|rerank|
                                                all_to_all|all_gather|
                                                broadcast
    repro_rag_retrievals_total{executor}        serve-layer retrieval queries
    repro_serve_batches_total{bucket,executor,shed}
                                                executed serving batches per
                                                pow2 shape bucket
    repro_serve_queries_total                   queries completed by the server
    repro_serve_rejected_total                  submits refused (queue full)
    repro_serve_shed_total{action}              overload sheds (action=nprobe)
    repro_serve_deadline_expired_total{where}   where=queue|result
    repro_serve_maintenance_total{event}        event=swap|discard — version-
                                                fenced background repack
                                                adoptions vs stale clones
    repro_serve_queue_depth                     gauge, admission queue depth
    repro_serve_jit_compiles                    gauge, process-wide XLA
                                                compiles observed (the
                                                zero-recompile-after-warmup
                                                gate)
    repro_serve_queue_wait_seconds              histogram, submit -> execution
    repro_host_stalls_total                     sampler wake-ups more than
                                                100 ms late (obs.host)
    repro_host_stall_seconds_total              their lateness, summed
    repro_host_gc_seconds_total{generation}     time inside gc collections

(``repro_store_mutations_total`` also records ``op=adopt`` — a background
repack swapped in by ``MutablePDXStore.adopt``.)

Span taxonomy
-------------
One ``QueryTrace`` per ``VectorSearchEngine.search`` call (the root covers
the whole call); phases nest under it:

    plan    planner dispatch (``core.plan.plan_search``)
    route   IVF bucket ranking + exchange planning (adaptive per-query
            routing, or ``route_batch``/``plan_routing``/send-buffer packing
            on the routed path)
    scan    executor body — device work fenced by ``block_until_ready``
            (every executor returns host arrays, so the span wall includes
            device completion)
    rerank  exact f32 re-rank of reduced-precision candidates; on sharded
            quantized paths it runs fused on-shard inside the scan and is
            recorded as a zero-width annotation span (``fused="on-shard"``)
    merge   write-head merge + final top-k assembly
    transform
            the batch's query rotation (the pruner's transform) in the
            ``fused-batch`` executor, inside ``scan``; host time to
            dispatch it, not fenced

Served queries (``repro.serve.vector``) cross threads: the trace is started
with ``trace.start_query`` when the executor takes the batch (its ``t0``),
bound on the executor thread with ``trace.use``, and finished after the
batch's futures are resolved.  Spans recorded with ``trace.span_at`` cover
the waits before it:

    queue     the oldest item's enqueue -> the executor's start
    admit     the oldest item's enqueue -> the batcher's drain returning
    plan      ``plan_search`` + ``prepare_execute`` on the batcher
    handoff   the end of ``plan`` -> the executor's start (the blocked put
              into the depth-1 hand-off queue and the time spent in it)

``admit``, ``plan`` and ``handoff`` tile ``queue`` up to the batcher's
work between the drain and planning (expiry check, stacking, padding).
After ``scan`` and ``merge`` comes

    deliver   the result copies and ``set_result`` calls; the callers'
              done-callbacks run inside it

The per-thread current trace plus the shared finished-trace ring make
concurrent worker traces land in one place.

Profiler annotations
--------------------
While telemetry is on, every span also opens a ``jax.profiler``
annotation named ``repro.<span>`` (``repro.plan``, ``repro.scan``,
``repro.deliver``, ...) on the thread doing the work, and work that runs
before a trace is bound is annotated with ``trace.activity``:
``repro.drain`` (the batcher waiting for the first item plus the flush
window), ``repro.plan`` and ``repro.handoff`` on the batcher,
``repro.await`` (the executor waiting for work).  ``repro.host_stall``
marks a stall the host sampler saw, at its end, with ``lost_ms``.  A
profiler trace thus shows the program's threads on the device's clock.

``SearchResult.trace`` carries the ``QueryTrace``;
``VectorSearchEngine.metrics()`` / ``dump_trace(path)`` surface the registry
snapshot and the Perfetto export.
"""
from . import host, metrics, trace

if metrics.enabled():  # REPRO_OBS=1: on from the start, sampler included
    host.start()

__all__ = ["metrics", "trace", "host", "meters"]
