"""repro.obs — the unified observability layer (tracing, metrics, sentinels).

Zero-dependency (stdlib only), disabled by default, and shared by every
tier: the fit path (``BrainEncoder``/``foldstats``), the streaming tier
(``ChunkPrefetcher``), the whole-brain column-blocked driver, and the
serving fleet (``EncoderService``/``EncoderRegistry``/``FleetFrontend``)
all emit through the SAME three primitives instead of bespoke stat dicts:

* **Spans** — ``with obs.span("fit.stats", bytes=n): ...`` nests, is
  thread-safe, stamps a monotonic clock, and exports to JSONL or
  Chrome/Perfetto ``trace_event`` JSON (``obs.write_trace``).  With no
  tracer installed the call returns a shared no-op after one module
  attribute load — hot paths stay permanently instrumented.
  ``obs.timed`` additionally ALWAYS measures (the streaming tier derives
  ``PrefetchStats`` stall seconds from the same measurement the span
  records); ``obs.transfer`` always counts a copy's bytes into a
  registry counter and records the same number on its span.
  ``obs.instant`` records zero-duration markers (reject/hit/evict).
* **Metrics** — ``obs.get_metrics()`` returns the process-global
  :class:`~repro.obs.metrics.MetricsRegistry`; ``obs.snapshot()`` renders
  every counter/gauge/histogram into one JSON dict (schema below) that
  ``stream_stats_``, ``ServiceStats.to_dict``, ``PrefetchStats.to_dict``
  and the ``BENCH_*.json`` rows embed.
* **Compile sentinels** — :class:`~repro.obs.sentinel.CompileCounter` is
  the one trace-time compile counter behind
  ``foldstats.chunk_update_compile_count``,
  ``wholebrain.colblock_update_compile_count`` and
  ``EncoderService.compile_count``; ``counter.expect(at_most=N)`` windows
  raise :class:`~repro.obs.sentinel.RecompileError` at trace time under
  ``REPRO_OBS_STRICT=1`` when a fixed-shape tier retraces.

Span naming convention: dotted ``<tier>.<phase>[.<subphase>]`` —
``fit.dispatch`` / ``fit.stats`` / ``fit.eigh`` / ``fit.solve``,
``fit.chunk.pad`` (a ragged chunk's zero-pad) / ``fit.chunk.h2d`` /
``fit.chunk.wait`` (each streamed chunk),
``fit.cv.solve`` / ``fit.cv.score`` (each fold, inside ``fit.eigh``,
whose ``method`` attribute says which factorisation ran, and on the
per-λ path ``bands`` and ``factor_update_flops`` which band plan),
``prefetch.stage`` / ``prefetch.wait`` / ``prefetch.compute_stall`` /
``prefetch.close``,
``wholebrain.block`` / ``wholebrain.xstats``, ``serve.wave.build`` /
``serve.wave.h2d`` / ``serve.wave.execute`` / ``serve.wave.sums_wait`` /
``serve.copyback`` (each carrying its ``wave`` number; ``serve.wave.build``
also ``reused``, 1 where the wave's host buffers were an earlier wave's,
and ``zeroed_rows``, the target rows it zeroed) and ``serve.results``,
``registry.load`` / ``registry.evict`` / ``registry.hit``,
``fleet.reject`` / ``fleet.flush`` (with its ``flush`` number).  A span
that times device work forces its outputs only while a tracer is
installed, so tracing off leaves the schedule untouched.

Metrics-snapshot schema (``obs.snapshot()``; version ``repro.obs/v1``)
----------------------------------------------------------------------

====================================  =========  ==========================================
key                                   type       meaning
====================================  =========  ==========================================
``schema``                            str        ``"repro.obs/v1"``
``counters``                          dict       flat ``name{label=v,...} -> float``
``gauges``                            dict       ``key -> {"value", "peak"}``
``histograms``                        dict       ``key -> {"count","sum","min","max","mean"}``
====================================  =========  ==========================================

Well-known instruments (all optional — present once the producing tier ran):

====================================  =========  ==========================================
instrument                            type       producer
====================================  =========  ==========================================
``compiles{tier=...}``                counter    every ``CompileCounter.mark`` (tiers:
                                                 ``foldstats.chunk_update``,
                                                 ``wholebrain.colblock_update``,
                                                 ``wholebrain.gram``, ``service.predict``)
``shard_partials{device=...}``        counter    row-shard ``FoldStats`` partials
                                                 finalised on each device
``bytes_staged``                      counter    prefetcher staging copies (bytes)
``chunks_staged``                     counter    prefetcher chunks staged
``read_stall_s`` / ``compute_stall_s``  counter  prefetcher stall seconds (consumer /
                                                 producer side)
``h2d_bytes{site=...}``               counter    bytes sent host → device (sites:
                                                 ``chunk``, a streamed chunk's
                                                 rows, targets and slot mask;
                                                 ``wave``, a serving wave's
                                                 inputs); also the ``bytes`` of
                                                 ``fit.chunk.h2d`` /
                                                 ``serve.wave.h2d``
``d2h_bytes{site=wave}``              counter    prediction bytes copied back
                                                 per wave; also the ``bytes``
                                                 of ``serve.copyback``
``wave_staging{outcome=...}``         counter    serving waves built in host buffers
                                                 an earlier wave had filled
                                                 (``reused``) or allocated for
                                                 this one (``allocated``)
``registry_hits`` / ``registry_loads``  counter  bundle cache hits / cold loads
``registry_evictions``                counter    LRU + fault evictions
``admitted_rows`` / ``rejected_requests``  counter  fleet admission outcomes
``io_retries{op=...}``                counter    transient faults retried by
                                                 ``resilience.retry_call`` (ops:
                                                 ``store.mmap``, ``prefetch.read``,
                                                 ``registry.load_encoder`` /
                                                 ``load_shard`` / ``load_std``)
``io_giveups{op=...}``                counter    retry budget exhausted — the original
                                                 error re-raised (typed at the caller)
``staging_reaped``                    counter    stale staging orphans swept by
                                                 ``resilience.reap_stale_staging``
``lease_expirations``                 counter    dead-worker leases reaped by
                                                 ``ResidencyMap.expire_dead``
``requests_replayed``                 counter    requests re-admitted after a
                                                 ``WorkerLost`` flush
``rss_bytes``                         gauge      resident set (background poller;
                                                 ``peak`` = observed high-water)
====================================  =========  ==========================================

Stats ``to_dict()`` payloads (``PrefetchStats``, ``ServiceStats``, and the
``stream_stats_`` dict) carry ``{"schema": "repro.obs/v1", "kind": ...}``
plus their flat snake_case fields — benches consume those dicts, never
raw attributes.  ``ServiceStats`` is the one account of waves, pad rows
and tenants; the registry holds no copy of it.

Surfacing: ``launch/encode.py``, ``launch/wholebrain.py`` and
``launch/serve.py`` accept ``--trace-out PATH`` (``.json`` → Perfetto,
else JSONL) and ``--metrics-out PATH``; ``launch/obs_report.py`` renders
a per-phase time/self-time/bytes table from a JSONL trace and can gate
span coverage (``--assert-coverage``).
"""
from repro.obs.metrics import (  # noqa: F401
    REGISTRY, SCHEMA_VERSION, Counter, Gauge, Histogram, MetricsRegistry,
    get_metrics, read_rss_bytes, snapshot, start_rss_poller,
)
from repro.obs.sentinel import (  # noqa: F401
    CompileCounter, RecompileError, strict_enabled,
)
from repro.obs.trace import (  # noqa: F401
    Tracer, current, install, instant, span, timed, transfer, uninstall,
    write_trace,
)

__all__ = [
    "Tracer", "span", "timed", "transfer", "instant", "install",
    "uninstall", "current", "write_trace",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "REGISTRY",
    "get_metrics", "snapshot", "start_rss_poller", "read_rss_bytes",
    "SCHEMA_VERSION",
    "CompileCounter", "RecompileError", "strict_enabled",
]
