"""repro.encoding — the unified brain-encoding estimator API.

This package is the front door to every ridge solver in the repo.  The
low-level solvers (``repro.core.ridge``/``mor``/``bmor``/``banded``) stay
available as the documented low-level layer, but call sites should not need
them: ``BrainEncoder`` picks the solver and mesh layout from the problem
shape using the paper's §3 analytic cost model (Eq. 6–7), and owns all
sharding boilerplate.

Quickstart::

    import jax
    from repro.encoding import BrainEncoder, pipeline
    from repro.data import fmri

    X, Y, mask = fmri.generate(jax.random.PRNGKey(0),
                               fmri.SubjectSpec(n=1200, p=128, t=512))
    state = pipeline.run(X, Y)            # detrend → split → fit → evaluate
    print(state.report.decision.solver)   # e.g. "ridge" (1 device) / "bmor"
    print(state.evaluation.mean_r, state.evaluation.significant)

Or, scikit-learn style, with explicit control::

    enc = BrainEncoder(solver="bmor", target_shards=8, n_folds=3)
    enc.fit(X_train, Y_train)
    r_per_target = enc.score(X_test, Y_test)      # Pearson r (paper §4.1)

Streaming large subjects (out-of-core)
--------------------------------------
The paper's whole-brain subjects (Table 1: n≈60k TRs × t≈264k targets)
cannot be materialised.  Write each run ONCE into an on-disk
``repro.data.store.RunStore`` (memory-mapped ``.npy`` shards + manifest),
then stream it::

    from repro.data.store import RunStore
    from repro.data import fmri

    store = RunStore.create("subj01_store")
    store.materialize_synthetic(fmri.SubjectSpec(n=500_000), seed=0)
    store = RunStore.open("subj01_store")          # read-only memmaps

    # 1. Transparent: give fit() a budget and the store — dispatch pins
    #    the streamed fold-statistics path (method="chunked") whenever
    #    the in-memory fit's estimate (materialized_fit_bytes) exceeds
    #    the budget, and
    #    shards the accumulation over the local devices (one psum of the
    #    stacked (k, p, p+t) partials at finalize).
    enc = BrainEncoder(device_memory_budget=2 * 2**30, chunk_rows=65536)
    enc.fit(store=store)                           # (n, p) never resident
    print(enc.report_.decision.rationale)

    # 2. Pipeline: two-pass streaming standardize (column μ/σ from one
    #    ColumnMoments pass) + fold-stats fit, no materialisation.
    state = pipeline.run_store(store, chunk_rows=65536)

    # 3. Explicit: BrainEncoder.fit_chunks accepts the store (or any
    #    ordered (X_chunk, Y_chunk) iterator) directly.
    enc = BrainEncoder(chunk_rows=65536).fit_chunks(store)

Evaluation needs rows that fit in memory — score against a separate held
-out store/array (``enc.evaluate(X_test, Y_test)``).  CV λ selection on
the streamed path is bit-identical to the in-memory fit (the chunk
-invariance harness in ``tests/test_oocore.py`` and the memory-capped CI
lane lock this down; ``BENCH_oocore.json`` tracks wall time / peak RSS).

The streaming tier is *overlapped*: by default (``EncoderConfig.prefetch``)
a background reader stages the NEXT chunk into a reusable host buffer —
bounded queue of ``prefetch_depth``, ``depth + 2`` staging buffers — while
the device accumulates the current one, so the disk→host→device→accumulate
pipeline runs at the speed of the slower side, not their sum.  Two
invariants make that free of semantic cost:

* **Prefetch is bit-identical.**  Staging is a straight copy; prefetch
  on/off select the same λ and produce the same weights bit for bit
  (``--no-prefetch`` on ``launch/encode.py`` is purely a wall-time A/B).
* **Fixed-shape masked updates compile ONCE.**  Every chunk — whatever
  its fold alignment, shard window, or ragged tail — is padded to
  ``chunk_rows`` and applied through one jitted masked einsum (fold
  membership is a per-row one-hot, pad rows an all-zero mask), so the
  accumulation's trace-time compile count is 1 instead of one per
  distinct fold-segment length.  ``foldstats.chunk_update_compile_count``
  exposes the counter; tests and the oocore bench gate on it.

After a streamed fit, ``enc.stream_stats_`` reports the overlap telemetry
(reader-stall vs compute-stall seconds, chunks, bytes staged, compiles).

Whole-brain target streaming
----------------------------
Row streaming bounds the ``n`` terms but still accumulates the full
``(k, p, t)`` fold statistics and solves all ``t`` targets at once — at
the paper's whole-brain ``t≈264k`` those target-axis arrays are what no
longer fit.  The third tier (``repro.wholebrain``) streams the TARGET
axis on top of the row tier: one shared pass accumulates the X-only
statistics (``G``, ``xsum``, ``count``), then each column block streams
its own ``(k, p, t_block)`` cross-moments through ONE fixed-shape
compiled update (ragged tail zero-padded to ``t_pad``), and the CV solve
reuses the per-fold eigendecompositions of the downdated Grams across
every block (the paper's Eq. 5 mutualisation, paid ``k+1`` times total,
not per block).  Peak memory is ``O(p² + p·t_block)`` — independent of
``t`` — and λ selection + weights stay BIT-identical to the unblocked
solve (``tests/test_wholebrain.py`` gates this across block widths,
f32 and bf16)::

    # Transparent: same budget knob — when even the row tier's t-axis
    # working set (k·p·(p+t) stats + (p, t) solve arrays) breaks the
    # budget, dispatch escalates to method="colblocked" and picks a
    # t_block that fits half the budget.  target_block= pins it.
    enc = BrainEncoder(device_memory_budget=2**30).fit(store=store)
    print(enc.report_.decision.method)          # "colblocked"

    # Explicit, with streaming artifact writes: weight shards land on
    # disk as blocks finish — W is NEVER resident all at once.
    from repro.wholebrain import BundleWriter, fit_wholebrain
    with BundleWriter("bundles/sub-01_wb", p=p, t=t) as w:
        res = fit_wholebrain(store, enc.config, t_block=16_384,
                             writer=w, collect=False)
        w.commit(config=enc.config, report=report,
                 lambda_by_target=res.lambda_by_target)

Serving reads the result lazily: ``EncoderBundle`` memory-maps weight
shards per column window (``load_weight_shard(i, mmap=True)``), and the
serving registry charges + pages in ONLY the shards a request window
touches, with LRU eviction at shard granularity.
``python -m repro.launch.wholebrain`` runs the whole loop on a
whole-brain-shaped synthetic subject under an RSS cap the unblocked
path cannot survive (``BENCH_wholebrain.json``).

The kernel tier (Pallas) is the default hot path
------------------------------------------------
The streamed masked chunk update — the inner loop of every tier above —
routes its heavy ``[G|C]`` contribution through the fused Pallas kernel
``kernels.gram.xty_folds_masked`` (one HBM pass: chunk in, per-fold
scatter out; the ``(k, m, p)`` masked intermediate never materialises).
``EncoderConfig.use_pallas`` is tri-state:

* ``None`` (default) — auto.  On where the backend compiles the kernels
  natively (TPU: they ARE the fast path), and on CPU only when
  ``REPRO_PALLAS_FORCE_INTERPRET=1`` is set — interpret mode runs the
  same code path as a correctness harness (the CI pallas lane), but is
  orders of magnitude slower than XLA, so plain CPU sessions stay on the
  einsum tier.
* ``True`` / ``False`` — pin it either way; explicit always wins.

``dispatch.resolve`` collapses the tri-state to a concrete
``DispatchDecision.use_pallas`` and names the choice in the rationale.
Both tiers present every chunk to the same fixed-shape jitted update
(``use_pallas`` is a static argument — each tier traces once), and λ
selection is bit-identical between them at f32
(``tests/test_fused_foldstats.py``; ``BENCH_foldstats.json`` carries the
fused-vs-unfused A/B with roofline placement)::

    enc = BrainEncoder()                      # auto: kernel tier on TPU
    enc = BrainEncoder(use_pallas=False)      # pin the einsum tier
    print(enc.report_.decision.use_pallas, enc.report_.decision.rationale)

Fit once, serve many
--------------------
A fitted encoder no longer dies with the process: ``save`` persists an
``EncoderBundle`` (sharded weights with bf16-as-u16 storage, the
pipeline's fitted μ/σ, selected λ per target, config + dispatch
provenance; atomic write, eagerly validated ``open``) and ``load``
rebuilds a predicting encoder bit-identically — no refit::

    enc = BrainEncoder().fit(X_train, Y_train)
    enc.save("bundles/sub-01_L12")
    enc2 = BrainEncoder.load("bundles/sub-01_L12")      # predicts ==
    enc_sh = BrainEncoder.load("bundles/sub-01_L12",    # serving layout:
                               target_shards=8)         # column-sharded W

Serving traffic against a fleet of bundles goes through
``repro.serving_encoders``: an ``EncoderRegistry`` lazy-loads bundles
under a ``device_memory_budget`` (LRU eviction), and an
``EncoderService`` micro-batches concurrent requests into fixed-shape
padded waves — one compiled ``standardize → X @ W → de-standardize``
program per wave shape, reused forever::

    from repro.serving_encoders import (EncoderRegistry, EncoderService,
                                        PredictRequest)
    reg = EncoderRegistry(device_memory_budget=512 * 2**20)
    reg.add("sub-01/L12", "bundles/sub-01_L12")
    service = EncoderService(reg, wave_rows=128)
    out = service.serve([PredictRequest("sub-01/L12", X_new,
                                        targets=Y_new)])   # + Pearson r

``python -m repro.launch.serve --encoders 3`` runs the whole loop
(materialise → fit → save → serve); ``BENCH_serving.json`` tracks
latency/throughput vs wave size.

The fleet tier scales that to N workers, one artifact dir, shared page
cache: each worker process runs a ``FleetRegistry`` (weight shards read
through read-only mmap, so co-located workers fault each shard from disk
once between them; per-process residency published into one file-locked
``residency.json``), the service packs scored AND unscored requests from
any tenants into the SAME mixed waves (per-row request one-hot → per-slot
Pearson sums, bit-identical to serving each request alone), and a
``FleetFrontend`` bounds admission in rows — overflow is a typed
``ServiceError`` rejection, never an OOM or a stall.  A bundle that
faults mid-serve (truncated shard, flipped manifest) degrades only its
own tenants: typed ``BundleError`` per affected request, eviction, and
the rest of the batch serves on.  ``python -m repro.launch.serve
--encoders 6 --workers 4`` drives the whole fleet;
``benchmarks/serving_bench.py --replay-trace`` gates p50/p99 and
bit-identity under the checked-in deterministic mixed-traffic trace.

Observing a fit and a fleet
---------------------------
Every tier above is permanently instrumented through ``repro.obs`` —
spans, metrics, and recompile sentinels — at zero cost until you opt in
(with no tracer installed a span site is one module attribute load).
Three switches:

* **Tracing**: install the process-global tracer around any code, or
  pass ``--trace-out PATH`` to ``launch/encode.py`` /
  ``launch/wholebrain.py`` / ``launch/serve.py`` (fleet parents and the
  wholebrain driver fan the flag out per worker/phase child)::

      from repro import obs
      tracer = obs.install()
      enc = BrainEncoder(device_memory_budget=1, chunk_rows=4096)
      enc.fit(store=store)            # fit.dispatch/stats/eigh/solve spans
      obs.write_trace(tracer, "fit.json")     # .json → open in Perfetto
      obs.uninstall()

  ``python -m repro.launch.obs_report fit.jsonl`` renders the per-phase
  time/bytes table and the root-coverage figure (the obs CI lane gates
  ≥95% of the fit root attributed to its phase children).
* **Metrics**: ``obs.snapshot()`` renders the process-global counters
  (``compiles{tier=...}``, ``bytes_staged``, ``h2d_bytes{site=...}``,
  ``d2h_bytes{site=wave}``, ``registry_hits``/``loads``/
  ``evictions``, fleet admission outcomes) plus the RSS high-water gauge
  into one schema'd dict (``repro.obs/v1``); ``--metrics-out PATH``
  writes it on launcher exit.  ``stream_stats_``,
  ``ServiceStats.to_dict()`` and ``PrefetchStats.to_dict()`` carry the
  same schema marker, and the ``BENCH_*.json`` rows embed them.
* **Sentinels**: under ``REPRO_OBS_STRICT=1`` every fixed-shape contract
  (the chunked fold update, the whole-brain column-block update, the
  serving wave programs) raises ``obs.RecompileError`` AT TRACE TIME if
  it retraces beyond its expectation window — the CI oocore, wholebrain,
  fleet, and obs lanes all run armed.

Surviving failures
------------------
A whole-brain fit is hours of streaming and a fleet runs unattended, so
the crash-safe tier (``repro.resilience``) assumes the process WILL die
and the disk WILL hiccup — and makes both survivable without changing a
single result bit:

* **Checkpoint/resume**: pass ``journal=`` to ``fit_wholebrain`` (or
  ``--journal`` to ``launch/wholebrain.py``) and every completed column
  block — plus the shared X-statistics pass — is committed to an
  atomic-rename ledger (payload → fsync → rename, ``ledger.json``
  rewritten last, torn ``*.tmp-*`` leftovers reaped on attach).  A
  killed fit re-attached to the same journal replays the committed
  blocks from disk (exact f32 stats, f64 score contributions added in
  block order) and streams only the remainder, so λ AND W come out
  BIT-identical to an uninterrupted run; a finished fit deletes its
  journal.  ``journal_signature`` pins the problem shape — a journal
  from a different fit raises ``JournalError`` instead of corrupting::

      from repro.wholebrain import fit_wholebrain
      res = fit_wholebrain(store, cfg, t_block=16_384,
                           journal="runs/sub-01.journal")
      res.telemetry["resumed"], res.telemetry["blocks_replayed"]

* **Transient-I/O retry**: ``RunStore.open(root, fault_policy=...)``
  arms every shard mmap, chunk read, and prefetcher stage with
  ``FaultPolicy`` retries — bounded attempts, exponential backoff with
  deterministic seeded jitter, optional per-op deadline, and a typed
  transient/permanent classifier (a permanent fault raises first time).
  The prefetcher's reader restarts its stream at the next unconsumed
  chunk, so a retried read is invisible downstream: λ, W, and the
  compile counts are unchanged (``tests/test_resilience.py`` injects
  mid-fit faults and gates exactly that).  ``EncoderRegistry`` takes the
  same ``fault_policy=`` for bundle/shard loads; exhausted retries
  surface as the usual typed ``StoreError``/``BundleError``.  Retries
  and give-ups are ``io_retries{op=...}`` / ``io_giveups{op=...}``
  counters with ``retry.backoff`` spans.

* **Fleet liveness**: every residency publish stamps a heartbeat lease
  (``ResidencyMap.heartbeat`` refreshes it between loads);
  ``expire_dead(ttl_s)`` reaps workers whose stamp went stale, so a
  SIGKILLed worker's claims vanish instead of pinning phantom residency
  forever.  ``holders(model, ttl_s=...)`` filters the stale rows on
  read.  A batch that dies with its worker is re-admitted by the
  frontend (``WorkerLost`` → pending restored in admission order,
  ``requests_replayed`` counted) and ``fleet.replay`` drains through the
  loss.  The map's file lock acquire is bounded too — a wedged peer
  yields a typed ``FleetError`` after ``lock_timeout_s``, never a hang.

* **Crashed-writer hygiene**: ``BundleWriter`` and store
  materialisation sweep stale staging leftovers (``.tmpbundle_*``,
  ``*.tmp-*``, …) past an age gate before writing
  (``resilience.reap_stale_staging``, ``staging_reaped`` counter).

All of it is driven by the seeded deterministic harness in
``repro.resilience.faultsim`` (fail the Nth read, truncate a payload,
kill after block N) — the CI ``faults`` lane runs the injection matrix,
a real ``--kill-after-block`` crash-resume smoke gating W shard bytes,
and a 2-worker drain with one worker SIGKILLed mid-trace.

Modules:
  config    — ``EncoderConfig``: one config subsuming ridge/banded/sharding
  dispatch  — complexity-driven solver + mesh-layout resolution
  sharding  — ``ShardingPlan``: mesh build, row rounding, device_put specs
  estimator — ``BrainEncoder`` / ``EncodingReport`` / ``EvaluationReport``
  pipeline  — composable detrend → split → standardize → fit → evaluate

(The target-axis tier itself lives in ``repro.wholebrain``: blocked
fold statistics, the mutualised column-blocked CV driver, and the
streaming ``BundleWriter``.)
"""
from repro.encoding import pipeline  # noqa: F401
from repro.encoding.config import EncoderConfig  # noqa: F401
from repro.encoding.dispatch import DispatchDecision, resolve  # noqa: F401
from repro.encoding.estimator import (  # noqa: F401
    BrainEncoder, EncodingReport, EvaluationReport,
)
from repro.encoding.sharding import ShardingPlan  # noqa: F401
