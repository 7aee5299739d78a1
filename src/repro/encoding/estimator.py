"""``BrainEncoder`` — the scikit-learn-style facade over every ridge solver.

One estimator, one result type.  ``fit`` resolves the solver through
``encoding.dispatch`` (unless pinned), owns all mesh/sharding boilerplate via
``encoding.sharding.ShardingPlan``, and normalises the four historical result
types (``RidgeCVResult``, ``BMORResult``, ``BandedResult``, bare MOR weight
matrices) into a single ``EncodingReport``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import banded, bmor, foldstats, mor, ridge, scoring
from repro.encoding.config import EncoderConfig
from repro.encoding.dispatch import DispatchDecision, resolve
from repro.encoding.sharding import ShardingPlan

_SOLVER_LABELS = {
    "ridge": "RidgeCV", "mor": "MOR", "bmor": "B-MOR",
    "bmor_dual": "dual B-MOR", "banded": "banded RidgeCV",
}


@dataclasses.dataclass
class EncodingReport:
    """Unified fit result across all solvers.

    ``best_lambda`` always has one entry per target batch: shape ``(1,)`` for
    single-shard solvers, ``(target_shards,)`` for B-MOR (per-batch λ,
    Alg. 1 line 13), ``(t,)`` conceptually for MOR (not materialised — MOR
    selects per target inside the fused program; the array is empty there).
    """

    weights: jax.Array                 # (p, t)
    best_lambda: np.ndarray            # (n_batches,) — see docstring
    cv_scores: np.ndarray              # (n_batches, r) CV curve per batch
    lambdas: tuple[float, ...]         # the swept grid (banded: empty)
    decision: DispatchDecision
    band_lambdas: np.ndarray | None = None   # (n_bands,), banded solver only

    @property
    def solver_label(self) -> str:
        return _SOLVER_LABELS[self.decision.solver]

    # -- machine-readable provenance ----------------------------------------
    def to_dict(self) -> dict:
        """Everything but the weight matrix, JSON-serialisable.

        The weights belong in an encoder *bundle* (they can be GBs); the
        report dict is the run provenance that rides next to it — solver
        decision, selected λ, CV curve, swept grid, weight shape/dtype.
        """
        return {
            "decision": dataclasses.asdict(self.decision),
            "best_lambda": np.asarray(self.best_lambda).tolist(),
            "cv_scores": np.asarray(self.cv_scores).tolist(),
            "lambdas": list(self.lambdas),
            "band_lambdas": (None if self.band_lambdas is None
                             else np.asarray(self.band_lambdas).tolist()),
            # None for a provenance-only report rebuilt via from_json.
            "weights_shape": (None if self.weights is None
                              else list(np.shape(self.weights))),
            "weights_dtype": (None if self.weights is None
                              else str(jnp.asarray(self.weights).dtype)),
            "solver_label": self.solver_label,
        }

    def to_json(self) -> str:
        import json
        return json.dumps(self.to_dict(), indent=2) + "\n"

    @classmethod
    def from_dict(cls, d: dict) -> "EncodingReport":
        """Rebuild the provenance half of a report (``weights`` is ``None``
        — load the bundle for the matrix itself)."""
        band = d.get("band_lambdas")
        return cls(
            weights=None,
            best_lambda=np.asarray(d["best_lambda"], np.float64),
            cv_scores=np.asarray(d["cv_scores"], np.float64),
            lambdas=tuple(d["lambdas"]),
            decision=DispatchDecision(**d["decision"]),
            band_lambdas=None if band is None else np.asarray(band))

    @classmethod
    def from_json(cls, s: str) -> "EncodingReport":
        import json
        return cls.from_dict(json.loads(s))


@dataclasses.dataclass
class EvaluationReport:
    """Held-out evaluation in the paper's metrics (§4.1–4.2)."""

    pearson_r: np.ndarray              # (t,) per-target test correlation
    r2: np.ndarray                     # (t,)
    null_r: np.ndarray                 # (n_perms, t) shuffled-stimulus control
    mean_r: float
    null_abs_r: float

    @property
    def significant(self) -> bool:
        """Aligned encoding clears the null floor (paper §4.2 criterion)."""
        return self.mean_r > 5.0 * self.null_abs_r


class BrainEncoder:
    """Multi-target brain-encoding ridge with automatic solver dispatch.

    >>> enc = BrainEncoder()                      # solver="auto"
    >>> enc.fit(X_train, Y_train)
    >>> r = enc.score(X_test, Y_test)             # per-target Pearson r
    >>> enc.report_.decision.solver               # what dispatch picked

    Keyword overrides are ``EncoderConfig`` fields:

    >>> BrainEncoder(solver="bmor", target_shards=8, n_folds=3)
    >>> BrainEncoder(bands=(4096, 4096))          # banded → per-band λ

    Attributes set by ``fit``: ``report_`` (an ``EncodingReport``),
    ``weights_`` (alias of ``report_.weights``).
    """

    def __init__(self, config: EncoderConfig | None = None, **overrides: Any):
        base = config or EncoderConfig()
        self.config = (dataclasses.replace(base, **overrides)
                       if overrides else base)
        self.report_: EncodingReport | None = None
        # Set by pipeline.standardize/fit (or by load()): the fitted
        # per-column μ/σ transform, persisted with save() so serving can
        # replay it on raw features.
        self.standardizer_ = None
        # Set by the streamed fit paths: overlap telemetry of the chunk
        # pipeline (reader-stall vs compute-stall seconds, chunks, bytes
        # staged, accumulation compile count).  None for in-memory fits.
        self.stream_stats_: dict | None = None

    # -- sklearn-ish surface -------------------------------------------------
    def fit(self, X: jax.Array | None = None, Y: jax.Array | None = None,
            *, store=None, chunk_rows: int | None = None) -> "BrainEncoder":
        """Fit from in-memory arrays, or out-of-core from a ``RunStore``.

        ``fit(X, Y)`` is the classic in-memory path.  ``fit(store=run_store)``
        resolves dispatch on the store's ``(n, p, t)`` shape: when the
        resident-set estimate exceeds ``config.device_memory_budget`` the
        decision pins ``method="chunked"`` and the rows are STREAMED from
        the memory-mapped shards (sharded over the local devices when
        ``data_shards > 1``) — ``(n, p)`` is never materialised; otherwise
        the store is loaded once and routed through the ordinary solver
        dispatch, so small stores transparently get B-MOR/dual/banded
        semantics.
        """
        with obs.span("fit", mode="store" if store is not None
                      else "arrays"):
            if store is not None:
                if X is not None or Y is not None:
                    raise ValueError("pass either (X, Y) or store=, not both")
                self._check_store_folds(store)
                n, p, t = store.shape
                with obs.span("fit.dispatch", n=n, p=p, t=t):
                    decision = resolve(self.config, n, p, t,
                                       jax.device_count())
                if decision.method == "colblocked":
                    return self._fit_store_colblocked(store, decision,
                                                      chunk_rows)
                if decision.method == "chunked":
                    return self._fit_store_chunked(store, decision,
                                                   chunk_rows)
                X, Y = store.load()
                X, Y = jnp.asarray(X), jnp.asarray(Y)
            if X is None or Y is None:
                raise ValueError("fit() needs (X, Y) arrays or store=")
            n, p = X.shape
            t = Y.shape[1]
            with obs.span("fit.dispatch", n=n, p=p, t=t):
                decision = resolve(self.config, n, p, t, jax.device_count())
            fitter = getattr(self, f"_fit_{decision.solver}")
            with obs.span("fit.solve", solver=decision.solver):
                self.report_ = fitter(X, Y, decision)
            return self

    def fit_chunks(self, chunks, n_total: int | None = None,
                   chunk_rows: int | None = None) -> "BrainEncoder":
        """Out-of-core fit from ordered ``(X_chunk, Y_chunk)`` row batches.

        The chunks are streamed through a ``foldstats.FoldStatsAccumulator``
        — only the ``(k, p, p+t)`` sufficient statistics ever live on the
        device, so ``X`` may be arbitrarily taller than device memory — and
        the CV'd solve runs entirely on the accumulated statistics
        (``ridge.ridge_cv_from_stats``).  Every chunk goes through ONE
        fixed-shape compiled masked update (padded to the chunk size, fold
        membership as a mask), so the whole stream costs a single trace
        regardless of fold alignment.  Primal/eigh single-shard only:
        the streaming regime is tall-``n``, exactly where the Gram form
        (p×p) is the small object.  Chunks must arrive in global row order;
        the fold split matches ``fit`` on the concatenated rows.

        ``chunks`` may also be a ``repro.data.store.RunStore``, or any
        source that reads like one (``shape``, ``n_folds``,
        ``iter_chunks``; ``repro.encoding.backbone.BackboneSource``) — it
        is streamed with ``config.chunk_rows`` (background-prefetched when
        ``config.prefetch``), ``n_total`` is taken from its shape, and the
        dispatch decision is resolved from that shape before the stream
        starts, as ``fit(store=)`` resolves it: the same kernel tier and
        the same solve.
        """
        self._check_chunkable()
        # A source that exposes PrefetchStats (a ChunkPrefetcher handed in
        # directly) contributes its overlap telemetry to stream_stats_.
        stream = chunks if hasattr(chunks, "stats") else None
        decision = None
        if hasattr(chunks, "iter_chunks"):            # RunStore duck-type
            self._check_store_folds(chunks)
            n_total, p, t = chunks.shape
            decision = resolve(self._streamed_config(), n_total, p, t,
                               jax.device_count())
            chunk_rows = chunk_rows or self.config.chunk_rows
            chunks = stream = chunks.iter_chunks(
                chunk_rows, prefetch=self.config.prefetch,
                prefetch_depth=self.config.prefetch_depth)
        if n_total is None:
            raise ValueError("fit_chunks needs n_total for iterator sources")
        use_pallas = (decision.use_pallas if decision is not None
                      else self.config.resolve_use_pallas())
        with obs.span("fit", mode="chunks"):
            compiles0 = foldstats.chunk_update_compile_count()
            with obs.span("fit.stats", n=n_total):
                stats = foldstats.compute_chunked(
                    chunks, n_total, self.config.n_folds,
                    chunk_rows=chunk_rows, use_pallas=use_pallas)
            self._record_stream_stats([stream] if stream is not None else [],
                                      compiles0)
            return self._fit_from_stats(stats, n_total, decision)

    def _check_store_folds(self, store) -> None:
        """The manifest's fold split is part of the store's data contract:
        every consumer must derive the identical k-fold assignment, so a
        config that disagrees with the manifest is an error, not a
        silently different CV."""
        k = getattr(store, "n_folds", None)
        if k is not None and k != self.config.n_folds:
            raise ValueError(
                f"store manifest records n_folds={k} but the encoder is "
                f"configured with n_folds={self.config.n_folds} — match "
                f"EncoderConfig.n_folds to the store (or re-create the "
                f"store with the intended split)")

    def _streamed_config(self) -> EncoderConfig:
        """The configuration a fit from streamed statistics resolves: the
        single-shard primal ridge solve."""
        return dataclasses.replace(self.config, solver="ridge", method="eigh")

    def _check_chunkable(self) -> None:
        if self.config.solver not in ("auto", "ridge"):
            raise ValueError(
                f"fit_chunks supports only the single-shard ridge solver; "
                f"solver={self.config.solver!r} is pinned — use fit() for "
                f"B-MOR/MOR/banded semantics")
        if self.config.method == "dual" or self.config.bands is not None:
            raise ValueError(
                "fit_chunks is primal/eigh only (streamed row statistics "
                "cannot build the dual kernel or per-band refits)")

    def _fit_from_stats(self, stats: foldstats.FoldStats, n_total: int,
                        decision: DispatchDecision | None = None
                        ) -> "BrainEncoder":
        """CV'd solve from accumulated fold statistics alone."""
        p, t = stats.G.shape[1], stats.C.shape[2]
        # Statistics-based CV scores lose f32 precision roughly
        # quadratically in |ȳ|/σ_y (see foldstats.validation_scores_from
        # _stats); refuse clearly pathological un-standardized targets
        # instead of returning silently corrupted scores.
        # The host pulls below block on the accumulation's async tail, so
        # under tracing this span is where the streamed compute drains.
        with obs.span("fit.finalize", n=n_total, t=t):
            mu = np.asarray(jnp.sum(stats.ysum, axis=0)) / n_total
            var = np.asarray(jnp.sum(stats.ysq, axis=0)) / max(n_total - 1, 1)
            ratio = float(np.max(np.abs(mu) / np.sqrt(var + 1e-12)))
        if ratio > 1e3:
            raise ValueError(
                f"fit_chunks: target mean/std ratio {ratio:.0f} is too "
                f"large for statistics-based CV scoring in float32 — "
                f"standardize the targets first (pipeline.standardize)")
        cfg = self._streamed_config()
        if decision is None:
            decision = resolve(cfg, n_total, p, t, jax.device_count())
        res = ridge.ridge_cv_from_stats(stats,
                                        cfg.ridge_cv_config("eigh"))
        self.report_ = EncodingReport(
            weights=res.weights,
            best_lambda=np.asarray(res.best_lambda)[None],
            cv_scores=np.asarray(res.cv_scores)[None, :],
            lambdas=self.config.lambdas, decision=decision)
        return self

    def _fit_store_chunked(self, store, decision: DispatchDecision,
                           chunk_rows: int | None) -> "BrainEncoder":
        """Streamed fit: shard the row windows over the local devices, each
        shard accumulating its own chunks; one psum combines the stacks.

        Each shard's stream is background-prefetched (``config.prefetch``;
        reader threads and staging buffers start lazily, so the sequential
        shard consumption only ever holds one prefetcher's buffers), and
        all shards share the one fixed-shape compiled update.  After the
        fit, ``stream_stats_`` records the overlap telemetry: reader-stall
        vs compute-stall seconds, chunks, bytes staged, and the trace-time
        compile count of the accumulation.
        """
        self._check_chunkable()
        n_total = store.shape[0]
        chunk_rows = chunk_rows or self.config.chunk_rows
        n_shards = max(1, min(decision.data_shards, jax.device_count(),
                              n_total))
        mesh = None
        if n_shards > 1:
            from repro.core.compat import make_mesh
            mesh = make_mesh((n_shards,), (self.config.data_axis,))
        streams = [
            store.iter_chunks(chunk_rows, row_range=(lo, hi),
                              prefetch=self.config.prefetch,
                              prefetch_depth=self.config.prefetch_depth)
            for lo, hi in foldstats.shard_row_ranges(n_total, n_shards)]
        compiles0 = foldstats.chunk_update_compile_count()
        with obs.span("fit.stats", n=n_total, shards=n_shards,
                      chunk_rows=chunk_rows):
            stats = foldstats.compute_sharded_chunked(
                streams, n_total, self.config.n_folds, mesh=mesh,
                data_axis=self.config.data_axis, chunk_rows=chunk_rows,
                use_pallas=decision.use_pallas)
        self._record_stream_stats(streams, compiles0)
        return self._fit_from_stats(stats, n_total, decision)

    def _fit_store_colblocked(self, store, decision: DispatchDecision,
                              chunk_rows: int | None) -> "BrainEncoder":
        """Target-axis streamed fit (``repro.wholebrain``): shared Gram
        pass + per-block ``(k, p, t_block)`` statistics, eigendecompositions
        reused across blocks.  λ and ``W`` are bit-identical to the
        unblocked statistics solve (global-λ mode).

        This transparent route still assembles the host ``(p, t)`` weight
        matrix for ``report_`` — at true whole-brain scale drive
        ``wholebrain.fit_wholebrain`` directly with a ``BundleWriter`` so
        the shards stream to disk instead (``launch/wholebrain.py``).
        """
        self._check_chunkable()
        from repro.wholebrain.solver import fit_wholebrain

        res = fit_wholebrain(store, self.config,
                             t_block=decision.target_block,
                             chunk_rows=chunk_rows)
        self.report_ = EncodingReport(
            weights=jnp.asarray(res.weights),
            best_lambda=res.best_lambda,
            cv_scores=res.cv_scores,
            lambdas=self.config.lambdas, decision=decision)
        self.stream_stats_ = {"schema": obs.SCHEMA_VERSION, "kind": "stream",
                              "prefetch": bool(self.config.prefetch),
                              **res.telemetry,
                              "compile_count":
                                  res.telemetry["colblock_compile_delta"]}
        return self

    def _record_stream_stats(self, streams, compiles_before: int) -> None:
        """Aggregate per-stream prefetch telemetry into ``stream_stats_``
        (the shared ``repro.obs`` snapshot schema: flat snake_case keys
        plus ``schema``/``kind`` markers)."""
        agg = {"schema": obs.SCHEMA_VERSION, "kind": "stream",
               "prefetch": bool(self.config.prefetch), "chunks": 0,
               "bytes_staged": 0, "read_stall_s": 0.0,
               "compute_stall_s": 0.0,
               "use_pallas": self.config.resolve_use_pallas(),
               "compile_count": (foldstats.chunk_update_compile_count()
                                 - compiles_before)}
        for stream in streams:
            s = getattr(stream, "stats", None)
            if s is None:
                continue
            d = s.to_dict()
            agg["chunks"] += d["chunks"]
            agg["bytes_staged"] += d["bytes_staged"]
            agg["read_stall_s"] += d["read_stall_s"]
            agg["compute_stall_s"] += d["compute_stall_s"]
        self.stream_stats_ = agg

    @property
    def weights_(self) -> jax.Array:
        assert self.report_ is not None, "call fit() first"
        return self.report_.weights

    # -- persistence (fit once, serve many) ----------------------------------
    def save(self, bundle_dir: str, *, overwrite: bool = False,
             weight_shards: int | None = None,
             weight_dtype: str | None = None,
             provenance: dict | None = None) -> str:
        """Persist the fitted encoder as an ``EncoderBundle`` directory.

        Everything needed to ``predict`` without refitting lands on disk:
        the weight matrix (column-sharded ``.npy`` leaves, bf16 stored as
        u16 bit patterns), the selected λ / CV provenance, the
        ``EncoderConfig``, the dispatch decision, and the fitted
        ``Standardizer`` when the pipeline attached one.  The write is
        atomic (tmp dir + rename).  Round-trip contract:
        ``BrainEncoder.load(d).predict(X)`` is bit-identical to
        ``self.predict(X)``.
        """
        from repro.serving_encoders import bundle as _bundle
        return _bundle.save_bundle(bundle_dir, self, overwrite=overwrite,
                                   weight_shards=weight_shards,
                                   weight_dtype=weight_dtype,
                                   provenance=provenance)

    @classmethod
    def load(cls, bundle_dir: str, *,
             target_shards: int | None = None) -> "BrainEncoder":
        """Rebuild a fitted encoder from a saved bundle (no refit).

        ``target_shards`` > 1 places the weight matrix column-sharded over
        a fresh ``(1, target_shards)`` mesh at load time (the serving
        layout); default is a single replicated device array.
        """
        from repro.serving_encoders import bundle as _bundle
        return _bundle.EncoderBundle.open(bundle_dir).load_encoder(
            target_shards=target_shards)

    def predict(self, X: jax.Array) -> jax.Array:
        return ridge.predict(X, self.weights_)

    def score(self, X: jax.Array, Y: jax.Array) -> np.ndarray:
        """Per-target Pearson r on held-out data (the paper's metric)."""
        return np.asarray(scoring.pearson_r(Y, self.predict(X)))

    def evaluate(self, X: jax.Array, Y: jax.Array, *, n_perms: int = 10,
                 key: jax.Array | None = None) -> EvaluationReport:
        """Pearson r + R² + the §4.2 null-permutation control."""
        preds = self.predict(X)
        r = np.asarray(scoring.pearson_r(Y, preds))
        r2 = np.asarray(scoring.r2_score(Y, preds))
        if key is None:
            key = jax.random.PRNGKey(self.config.seed + 1)
        null = np.asarray(scoring.null_permutation_scores(
            key, X, Y, self.weights_, n_perms=n_perms))
        return EvaluationReport(
            pearson_r=r, r2=r2, null_r=null, mean_r=float(r.mean()),
            null_abs_r=float(np.abs(null).mean()))

    # -- per-solver fit paths ------------------------------------------------
    def _fit_ridge(self, X, Y, decision: DispatchDecision) -> EncodingReport:
        res = ridge.ridge_cv(X, Y, self.config.ridge_cv_config(decision.method))
        return EncodingReport(
            weights=res.weights,
            best_lambda=np.asarray(res.best_lambda)[None],
            cv_scores=np.asarray(res.cv_scores)[None, :],
            lambdas=self.config.lambdas, decision=decision)

    def _fit_mor(self, X, Y, decision: DispatchDecision) -> EncodingReport:
        cfg = self.config.ridge_cv_config(decision.method)
        if self.config.mor_taskwise and decision.target_shards > 1:
            # Distributed MOR is one fused XLA program per shard, which hoists
            # the per-target refactorisation (see mor.mor_fit's NOTE) — the
            # opposite of what the taskwise flag exists to measure.
            raise ValueError("mor_taskwise=True is incompatible with "
                             "target_shards > 1: taskwise MOR is a host-level "
                             "per-target loop (paper Fig. 8 cost semantics)")
        if decision.target_shards > 1:
            plan = ShardingPlan(data_shards=1,
                                target_shards=decision.target_shards,
                                data_axis=self.config.data_axis,
                                target_axis=self.config.target_axis)
            X, Y, t = plan.prepare(X, Y)
            W = mor.mor_fit_distributed(X, Y, plan.build_mesh(),
                                        axis=plan.target_axis, cfg=cfg)
            W = W[:, :t]
        elif self.config.mor_taskwise:
            W = mor.mor_fit_taskwise(X, Y, cfg)
        else:
            W = mor.mor_fit(X, Y, cfg)
        return EncodingReport(
            weights=W,
            best_lambda=np.empty((0,)),          # per-target λ stays internal
            cv_scores=np.empty((0, len(self.config.lambdas))),
            lambdas=self.config.lambdas, decision=decision)

    def _fit_bmor(self, X, Y, decision: DispatchDecision) -> EncodingReport:
        plan = ShardingPlan(data_shards=decision.data_shards,
                            target_shards=decision.target_shards,
                            data_axis=self.config.data_axis,
                            target_axis=self.config.target_axis)
        X, Y, t = plan.prepare(X, Y)
        mesh = plan.build_mesh()
        Xs, Ys = plan.place(mesh, X, Y)
        res = bmor.bmor_fit(Xs, Ys, mesh, data_axis=plan.data_axis,
                            target_axis=plan.target_axis,
                            cfg=self.config.ridge_cv_config("eigh"))
        return EncodingReport(
            weights=res.weights[:, :t],
            best_lambda=np.asarray(res.best_lambda),
            cv_scores=np.asarray(res.cv_scores),
            lambdas=self.config.lambdas, decision=decision)

    def _fit_bmor_dual(self, X, Y, decision: DispatchDecision
                       ) -> EncodingReport:
        plan = ShardingPlan(data_shards=1,
                            target_shards=decision.target_shards,
                            data_axis=self.config.data_axis,
                            target_axis=self.config.target_axis,
                            replicate_rows=True)
        X, Y, t = plan.prepare(X, Y)
        mesh = plan.build_mesh()
        Xs, Ys = plan.place(mesh, X, Y)
        res = bmor.bmor_fit_dual(Xs, Ys, mesh, target_axis=plan.target_axis,
                                 cfg=self.config.ridge_cv_config("dual"))
        return EncodingReport(
            weights=res.weights[:, :t],
            best_lambda=np.asarray(res.best_lambda),
            cv_scores=np.asarray(res.cv_scores),
            lambdas=self.config.lambdas, decision=decision)

    def _fit_banded(self, X, Y, decision: DispatchDecision) -> EncodingReport:
        bands = self.config.bands
        if sum(bands) != X.shape[1]:
            raise ValueError(f"bands {bands} sum to {sum(bands)} but X has "
                             f"{X.shape[1]} features")
        res = banded.banded_ridge_cv(jax.random.PRNGKey(self.config.seed),
                                     X, Y, self.config.banded_config())
        return EncodingReport(
            weights=res.weights,
            best_lambda=np.empty((0,)),          # per-band, not per-grid-λ
            cv_scores=np.asarray(res.cv_scores)[None, :],
            lambdas=(), decision=decision,
            band_lambdas=np.asarray(res.band_lambdas))
