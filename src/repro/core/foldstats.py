"""Single-pass fold-aware Gram statistics (CV by downdating, not recompute).

The paper's mutualisation lever (Eq. 4-5) computes the expensive statistics
once and reuses them across all targets and all λ — but k-fold CV naively
re-accumulates the Gram matrix ``G_train = X_trᵀX_tr`` for every split,
paying the dominant ``T_W = O(np²)`` term ``k`` times (each split covers
``(k-1)/k`` of the rows, so the total is ``(k-1)·np²`` plus ``np²`` for the
full-data refit).

This module reformulates CV on *sufficient statistics*: every per-fold
partial statistic

    G_f = X_fᵀX_f        C_f = X_fᵀY_f        (plus first/second moments)

is accumulated in ONE streaming pass over the rows — each row enters exactly
one fold's accumulator — and every training-split statistic is then derived
by subtraction (the Gram downdate identity, exact in exact arithmetic):

    G_train(f) = Σ_g G_g − G_f        C_train(f) = Σ_g C_g − C_f

The full-data refit statistics are the sums themselves, so a complete
k-fold CV + refit costs a single ``np²`` accumulation.  The same identity
is what makes the distributed B-MOR path a single ``psum`` over row shards
(``repro.core.bmor``); here it is factored out so the single-shard
``ridge.ridge_cv``, the dual path, B-MOR, and the Pallas kernel
(``repro.kernels.gram.xty_folds``) all consume one implementation.

The per-row moment statistics (``xsum``, ``ysum``, ``ysq``, ``count``) make
validation scores computable from the statistics alone (no validation-row
matrix needed): for weights ``W`` the held-out sums are ``Σŷ = xsum_fᵀW``,
``Σŷ² = diag(WᵀG_fW)``, ``Σyŷ = diag(C_fᵀW)`` — which is what opens the
out-of-core path (``FoldStatsAccumulator`` / ``BrainEncoder.fit_chunks``)
where ``X`` arrives as row batches larger than device memory.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.gram import PRECISION


def fold_bounds(n: int, n_folds: int) -> list[tuple[int, int]]:
    """Contiguous k-fold boundaries (static, trace-time).

    The first ``n % n_folds`` folds get the extra row, matching
    scikit-learn's ``KFold`` and the seed ``ridge._fold_bounds``.
    """
    if not 1 <= n_folds <= n:
        raise ValueError(f"need 1 <= n_folds <= n, got n_folds={n_folds}, "
                         f"n={n}")
    sizes = [n // n_folds + (1 if i < n % n_folds else 0)
             for i in range(n_folds)]
    bounds, start = [], 0
    for s in sizes:
        bounds.append((start, start + s))
        start += s
    return bounds


def fold_of_rows(row_ids: jax.Array, n_total: int, n_folds: int) -> jax.Array:
    """Contiguous fold id of each global row (same split as ``fold_bounds``).

    Traced-index variant for sharded rows, where a shard's slice of the
    global row range is only known at run time (``jax.lax.axis_index``).
    """
    base, rem = divmod(n_total, n_folds)
    # Rows [0, (base+1)*rem) live in folds of size base+1; the rest size base.
    big = (base + 1) * rem
    in_big = row_ids < big
    fold_big = row_ids // jnp.maximum(base + 1, 1)
    fold_small = rem + (row_ids - big) // jnp.maximum(base, 1)
    return jnp.where(in_big, fold_big, fold_small).astype(jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FoldStats:
    """Per-fold sufficient statistics of a supervised row stream.

    All statistics are f32 accumulations regardless of the input dtype
    (bf16/f32 inputs hit the MXU with ``preferred_element_type=float32``,
    the DESIGN §2 adaptation of the paper's float64 BLAS).
    """

    G: jax.Array        # (k, p, p)  per-fold XᵀX
    C: jax.Array        # (k, p, t)  per-fold XᵀY
    xsum: jax.Array     # (k, p)     per-fold Σ x
    ysum: jax.Array     # (k, t)     per-fold Σ y
    # Per-fold CENTRED second moment Σ (y − ȳ_f)², not the raw Σ y²: raw
    # second moments cancel catastrophically in f32 (ss_tot = Σy² − mȳ²)
    # for targets with large means, flipping λ selection on un-standardized
    # data.  The streaming accumulator maintains it with the Chan et al.
    # pairwise-combination update, so it stays exact under chunking.
    ysq: jax.Array      # (k, t)     per-fold Σ (y − ȳ_f)²
    count: jax.Array    # (k,)       per-fold row count

    @property
    def n_folds(self) -> int:
        return self.G.shape[0]

    @property
    def G_total(self) -> jax.Array:
        """Full-data Gram — the sums over folds ARE the refit statistics."""
        return jnp.sum(self.G, axis=0)

    @property
    def C_total(self) -> jax.Array:
        return jnp.sum(self.C, axis=0)

    def train(self, f: int) -> tuple[jax.Array, jax.Array]:
        """Downdated training statistics ``(G_tr, C_tr)`` for split ``f``.

        ``G_total − G_f`` equals ``X_trᵀX_tr`` exactly in exact arithmetic
        (it is a sum over disjoint row sets), so this is Algorithm 1's
        per-split factorisation input without re-touching the rows.
        """
        return self.G_total - self.G[f], self.C_total - self.C[f]


def _xty(X: jax.Array, Y: jax.Array) -> jax.Array:
    return jnp.matmul(X.T, Y, preferred_element_type=jnp.float32)


def compute(X: jax.Array, Y: jax.Array, n_folds: int, *,
            use_pallas: bool = False) -> FoldStats:
    """All per-fold statistics in one pass over the rows.

    Fold membership is contiguous and trace-time static (``fold_bounds``),
    so each fold's ``{G_f, C_f}`` is a matmul over exactly its own rows —
    no row is touched by more than one accumulation, no per-fold
    ``concatenate`` copy of ``X`` is made.  With ``use_pallas`` the fold
    tiles come from ``kernels.gram.xty_folds``, which streams HBM row
    blocks once and scatters each block's contribution to its fold's
    output tile.
    """
    n, p = X.shape
    bounds = fold_bounds(n, n_folds)
    if use_pallas:
        from repro.kernels import ops
        # One fused kernel invocation: Xᵀ[X | Y] per fold — a single repack
        # and a single HBM sweep of X instead of separate G and C passes.
        dt = jnp.promote_types(X.dtype, Y.dtype)
        Z = jnp.concatenate([X.astype(dt), Y.astype(dt)], axis=1)
        GC = ops.xty_folds(X.astype(dt), Z, tuple(bounds))
        G, C = GC[:, :, :p], GC[:, :, p:]
    else:
        G = jnp.stack([_xty(X[lo:hi], X[lo:hi]) for lo, hi in bounds])
        C = jnp.stack([_xty(X[lo:hi], Y[lo:hi]) for lo, hi in bounds])
    Xf = X.astype(jnp.float32)
    Yf = Y.astype(jnp.float32)
    xsum = jnp.stack([jnp.sum(Xf[lo:hi], axis=0) for lo, hi in bounds])
    ysum = jnp.stack([jnp.sum(Yf[lo:hi], axis=0) for lo, hi in bounds])
    ysq = jnp.stack([
        jnp.sum((Yf[lo:hi] - jnp.mean(Yf[lo:hi], axis=0)) ** 2, axis=0)
        for lo, hi in bounds])
    count = jnp.asarray([hi - lo for lo, hi in bounds], jnp.float32)
    return FoldStats(G=G, C=C, xsum=xsum, ysum=ysum, ysq=ysq, count=count)


def partial_fold_stats(X: jax.Array, Y: jax.Array, fold_ids: jax.Array,
                       n_folds: int) -> tuple[jax.Array, jax.Array]:
    """Per-fold ``{G_f, C_f}`` from traced fold membership (sharded rows).

    Inside ``shard_map`` a shard's global row range depends on
    ``axis_index`` — not trace-time static — so fold membership is a mask,
    not a slice.  Each fold costs a masked matmul over the local rows; the
    payoff is collective, not FLOP, economy: the stacked ``(k, p, ·)``
    result is ONE ``psum`` and the total/training statistics then derive
    by summation/downdating with no further collectives (B-MOR previously
    paid ``k+1`` psums of the same bytes).
    """
    def one(f: int) -> tuple[jax.Array, jax.Array]:
        m = (fold_ids == f).astype(X.dtype)[:, None]
        Xm = X * m
        return _xty(Xm, Xm), _xty(Xm, Y * m)
    per_fold = [one(f) for f in range(n_folds)]
    return (jnp.stack([g for g, _ in per_fold]),
            jnp.stack([c for _, c in per_fold]))


def _add_slots(acc: jax.Array, slot_fold: jax.Array,
               upd: jax.Array) -> jax.Array:
    """``acc[slot_fold[i]] += upd[i]`` for each slot, one fold slab at a
    time.  A dynamic-slice update of a donated buffer is done in place,
    where a scatter-add of the (k, p, ·) statistics plans a second copy of
    them; the per-slab adds are the same as the scatter's, so the result
    is bit for bit the same (duplicate slots add exact zeros)."""
    for i in range(upd.shape[0]):
        f = slot_fold[i]
        cur = jax.lax.dynamic_index_in_dim(acc, f, axis=0, keepdims=False)
        acc = jax.lax.dynamic_update_index_in_dim(acc, cur + upd[i], f,
                                                  axis=0)
    return acc


class _FixedShapeUpdate:
    """The ONE compiled program of the streaming accumulation.

    Every chunk — fold-aligned or not, full or ragged — is presented to
    this update as the SAME fixed shape: ``(chunk_rows, p)`` rows plus a
    per-row slot one-hot (``(chunk_rows, s_max)``; zero rows are padding)
    and the traced fold index of each slot.  The ``(k, p, p+t)`` partial
    update is then a single masked einsum + scatter-add, so the whole
    stream traces exactly once per ``(chunk_rows, p, t, k, s_max, dtype)``
    signature instead of once per distinct fold-segment length (the
    eager per-segment path recompiled at every fold boundary, ragged
    tail, and chunk/fold misalignment — a compile storm the oocore bench
    measured at >10 traces per stream).

    A chunk of ``chunk_rows`` contiguous rows intersects at most
    ``s_max = (chunk_rows − 2) // min_fold + 2`` folds, so the masked
    work is a small constant multiple (2 for ``chunk_rows ≤ min_fold``)
    of the unmasked matmul — paid once, unlike a recompile.  Unused
    slots carry an all-zero mask and contribute exact zeros through the
    scatter-``add``, so duplicate slot→fold indices are harmless.
    """

    def __init__(self) -> None:
        self.compiles = obs.CompileCounter("foldstats.chunk_update")
        # The running statistics are donated: the update writes them in
        # place, so the device holds one copy of them, not two.
        self._fn = jax.jit(self._update, static_argnames=("use_pallas",),
                           donate_argnums=0)

    @property
    def compile_count(self) -> int:
        return self.compiles.count

    def __call__(self, stats: FoldStats, X, Y, onehot, slot_fold, *,
                 use_pallas: bool = False) -> FoldStats:
        return self._fn(stats, X, Y, onehot, slot_fold,
                        use_pallas=use_pallas)

    def _update(self, stats: FoldStats, X: jax.Array, Y: jax.Array,
                onehot: jax.Array, slot_fold: jax.Array,
                use_pallas: bool = False) -> FoldStats:
        # Python side effect at TRACE time only: counts actual program
        # builds, the O(1)-compiles contract tests and the oocore bench
        # assert on.  Under REPRO_OBS_STRICT=1 an open expect() window
        # turns an excess trace into a RecompileError right here.
        self.compiles.mark()
        with jax.named_scope("chunk_update"):
            p = X.shape[1]
            dt = jnp.promote_types(X.dtype, Y.dtype)
            # One fused Xᵀ[X | Y] per slot — one batched GEMM per chunk.
            Z = jnp.concatenate([X.astype(dt), Y.astype(dt)], axis=1)
            w = onehot                                      # (m, s) f32 0/1
            if use_pallas:
                # Kernel tier: mask + Gram + cross-covariance fused into one
                # VMEM-resident blocked reduction — one HBM pass per chunk,
                # the (s, m, p) masked intermediate never materialised.
                from repro.kernels import ops
                GC = ops.xty_folds_masked(X.astype(dt), Z,
                                          w.astype(dt))         # (s, p, p+t)
            else:
                Xw = (X.astype(dt)[None]
                      * jnp.swapaxes(w, 0, 1)[:, :, None].astype(dt))
                GC = jnp.einsum("smp,mq->spq", Xw, Z, precision=PRECISION,
                                preferred_element_type=jnp.float32)
            Xf, Yf = X.astype(jnp.float32), Y.astype(jnp.float32)
            cnt = jnp.sum(w, axis=0)                             # (s,)
            xsum = jnp.einsum("ms,mp->sp", w, Xf, precision=PRECISION,
                              preferred_element_type=jnp.float32)
            ysum = jnp.einsum("ms,mt->st", w, Yf, precision=PRECISION,
                              preferred_element_type=jnp.float32)
            # Chan et al. pairwise combination of the centred second
            # moment: M2_{a∪b} = M2_a + M2_b + (μ_a − μ_b)²·n_a n_b/(n_a+n_b)
            # — exact, and free of the Σy² − mȳ² cancellation.  Per-slot
            # quantities are gathered from / scattered back to each slot's
            # fold; an empty slot has cnt = 0 so every one of its additions
            # is exactly 0.
            mu_b = ysum / jnp.maximum(cnt, 1.0)[:, None]
            d = Yf[None, :, :] - mu_b[:, None, :]                # (s, m, t)
            m2 = jnp.einsum("ms,smt->st", w, d * d, precision=PRECISION,
                            preferred_element_type=jnp.float32)
            n_a = stats.count[slot_fold]                         # (s,)
            mu_a = stats.ysum[slot_fold] / jnp.maximum(n_a, 1.0)[:, None]
            both = ((n_a > 0) & (cnt > 0))[:, None]
            delta2 = jnp.where(both, (mu_a - mu_b) ** 2, 0.0)
            ysq_add = m2 + delta2 * (n_a * cnt
                                     / jnp.maximum(n_a + cnt, 1.0))[:, None]
            return FoldStats(
                G=_add_slots(stats.G, slot_fold, GC[:, :, :p]),
                C=_add_slots(stats.C, slot_fold, GC[:, :, p:]),
                xsum=stats.xsum.at[slot_fold].add(xsum),
                ysum=stats.ysum.at[slot_fold].add(ysum),
                ysq=stats.ysq.at[slot_fold].add(ysq_add),
                count=stats.count.at[slot_fold].add(cnt))


# Module-level singleton: shards and repeated streams share one jit cache,
# so e.g. 8 shard accumulators with identical chunk shapes cost ONE trace.
_FIXED_UPDATE = _FixedShapeUpdate()


def chunk_update_compile_count() -> int:
    """Trace count of the fixed-shape chunk update (monotonic, process-wide).

    Take a delta around a stream to measure its compiles; the contract is
    ``delta == 1`` for a fresh ``(chunk_rows, p, t, k)`` signature and
    ``0`` for a repeat, regardless of fold alignment or ragged tails.

    (Thin alias over ``chunk_update_compiles().count`` — the shared
    ``obs.CompileCounter`` primitive; kept so existing gates read the
    same number they always did.)
    """
    return _FIXED_UPDATE.compiles.count


def chunk_update_compiles() -> "obs.CompileCounter":
    """The chunk update's :class:`repro.obs.CompileCounter` — open an
    ``expect(at_most=...)`` window around a stream to arm the recompile
    sentinel (raises at trace time under ``REPRO_OBS_STRICT=1``)."""
    return _FIXED_UPDATE.compiles


def _pad_rows(a, rows: int):
    """``a`` zero-padded to ``rows`` rows: on the device for a device
    array (a chunk made there never visits the host), else on the host."""
    import numpy as np
    if isinstance(a, jax.Array):
        return jnp.pad(a, ((0, rows - a.shape[0]), (0, 0)))
    out = np.zeros((rows, a.shape[1]), np.asarray(a).dtype)
    out[:a.shape[0]] = a
    return out


def send_chunk(put, Xs, Ys, onehot, slot_fold):
    """``(put(Xs), put(Ys))`` inside one ``fit.chunk.h2d`` span.

    The span's ``bytes`` (and the ``h2d_bytes{site=chunk}`` counter) are
    the padded chunk as sent: its rows and targets, and the slot mask the
    update's call carries.  While a tracer is installed the span waits
    for the two copies, so it times the transfer; with none installed
    nothing here waits.
    """
    nbytes = int(sum(a.nbytes for a in (Xs, Ys, onehot, slot_fold)
                     if not isinstance(a, jax.Array)))
    with obs.transfer("fit.chunk.h2d", "h2d_bytes", nbytes, site="chunk",
                      rows=int(Xs.shape[0])):
        X, Y = put(Xs), put(Ys)
        if obs.current() is not None:
            jax.block_until_ready((X, Y))
    return X, Y


class FoldStatsAccumulator:
    """Streaming builder of ``FoldStats`` from ordered row chunks.

    The out-of-core entry point (``BrainEncoder.fit_chunks``): rows arrive
    as host-sized batches; each batch is padded to one fixed chunk shape
    and applied through the single jitted masked update
    (``_FixedShapeUpdate``) — fold boundaries, ragged tails, and
    chunk/fold misalignment change only the mask contents, never the
    compiled program.  Rows must arrive in global row order; ``finalize``
    checks that exactly the owned row window was seen.

    ``chunk_rows`` pins the fixed shape up front (what the store-streaming
    callers do, so every shard shares one program signature); when omitted
    it is inferred from the first chunk.  Oversized batches are split,
    undersized ones zero-padded — the pad rows carry an all-zero mask, so
    they contribute exact zeros to every statistic.

    ``row_start``/``row_stop`` restrict the accumulator to a contiguous
    window of the global rows — the sharded out-of-core path gives each
    shard its own window (``shard_row_ranges``) and combines the partial
    ``FoldStats`` afterwards (``combine`` / ``compute_sharded_chunked``).
    Fold membership always derives from the GLOBAL ``(n_total, n_folds)``
    split, so a shard boundary in the middle of a fold is handled exactly:
    the fold's statistics simply arrive as two partials that ``combine``
    merges with the Chan update.
    """

    def __init__(self, n_total: int, n_folds: int, *, row_start: int = 0,
                 row_stop: int | None = None,
                 chunk_rows: int | None = None,
                 use_pallas: bool = False, device=None):
        self.n_total = n_total
        # Where the statistics live and the chunks are staged (None: the
        # default device) — a row shard's accumulator sits on its chip.
        self.device = device
        # Kernel-tier flag for the heavy [G|C] contribution.  Static under
        # the jit, so fused and unfused streams are distinct signatures —
        # each still traces exactly once (the compile_count contract is
        # per signature, and a process never mixes tiers mid-stream).
        self.use_pallas = use_pallas
        self.bounds = fold_bounds(n_total, n_folds)
        self.row_start = row_start
        self.row_stop = n_total if row_stop is None else row_stop
        if not 0 <= self.row_start < self.row_stop <= n_total:
            raise ValueError(
                f"need 0 <= row_start < row_stop <= n_total, got "
                f"[{row_start}, {row_stop}) with n_total={n_total}")
        if chunk_rows is not None and chunk_rows < 1:
            raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
        self._offset = self.row_start
        self._stats: FoldStats | None = None
        # Fixed shape of the compiled update: pin to the caller's chunk
        # size (never more than the data) or infer from the first chunk.
        self._fixed_rows = (None if chunk_rows is None
                            else min(chunk_rows, n_total))

    def _init_stats(self, p: int, t: int) -> FoldStats:
        k = len(self.bounds)

        def z(shape, dtype):
            return jnp.zeros(shape, dtype, device=self.device)

        return FoldStats(G=z((k, p, p), jnp.float32),
                         C=z((k, p, t), jnp.float32),
                         xsum=z((k, p), jnp.float32),
                         ysum=z((k, t), jnp.float32),
                         ysq=z((k, t), jnp.float32),
                         count=z((k,), jnp.float32))

    def _max_slots(self) -> int:
        """Folds a ``_fixed_rows`` window can intersect: it fully contains
        every fold but its two ends, each of size ≥ ``min_fold``."""
        min_fold = min(hi - lo for lo, hi in self.bounds)
        return min(len(self.bounds),
                   max(1, (self._fixed_rows - 2) // min_fold + 2))

    def _slot_mask(self, m: int) -> tuple:
        """(onehot (fixed, s_max) f32, slot_fold (s_max,) i32) for the
        ``m`` valid rows at the current offset (pad rows all-zero)."""
        import numpy as np
        s_max = self._max_slots()
        onehot = np.zeros((self._fixed_rows, s_max), np.float32)
        slot_fold = np.zeros((s_max,), np.int32)
        s = 0
        for f, (lo, hi) in enumerate(self.bounds):
            seg_lo = max(lo, self._offset) - self._offset
            seg_hi = min(hi, self._offset + m) - self._offset
            if seg_lo >= seg_hi:
                continue
            assert s < s_max, "slot bound violated (fold split bug)"
            onehot[seg_lo:seg_hi, s] = 1.0
            slot_fold[s] = f
            s += 1
        return onehot, slot_fold

    def _apply(self, Xs, Ys, onehot, slot_fold) -> None:
        """Apply one fixed-shape padded chunk to the running statistics.

        The single overridable seam of the streaming machinery: subclasses
        that accumulate a different statistic from the same masked chunks
        (``repro.wholebrain.ColumnBlockAccumulator``) replace only this —
        splitting, padding, slot masks, offsets, and the finalize contract
        stay shared.
        """
        X, Y = send_chunk(lambda a: jax.device_put(a, self.device),
                          Xs, Ys, onehot, slot_fold)
        self._stats = _FIXED_UPDATE(self._stats, X, Y, onehot, slot_fold,
                                    use_pallas=self.use_pallas)

    def update(self, X_chunk: jax.Array, Y_chunk: jax.Array) -> None:
        import numpy as np
        m = X_chunk.shape[0]
        if self._offset + m > self.row_stop:
            raise ValueError(
                f"chunk of {m} rows at offset {self._offset} overruns "
                f"row_stop={self.row_stop}")
        if self._stats is None:
            self._stats = self._init_stats(X_chunk.shape[1],
                                           Y_chunk.shape[1])
        if self._fixed_rows is None:
            self._fixed_rows = m
        fixed = self._fixed_rows
        lo = 0
        while lo < m:                       # oversized batches: split
            hi = min(lo + fixed, m)
            Xs, Ys = X_chunk[lo:hi], Y_chunk[lo:hi]
            if hi - lo < fixed:             # ragged: zero-pad to the shape
                with obs.span("fit.chunk.pad", rows=hi - lo):
                    Xs, Ys = _pad_rows(Xs, fixed), _pad_rows(Ys, fixed)
            onehot, slot_fold = self._slot_mask(hi - lo)
            self._apply(Xs, Ys, onehot, slot_fold)
            self._offset += hi - lo
            lo = hi
        # Synchronize before returning: jnp.asarray's host→device transfer
        # is ASYNC, and a prefetched source recycles its staging buffer as
        # soon as the next chunk is requested — returning with the copy
        # still in flight would let the reader overwrite rows the update
        # has not yet consumed.  Blocking on the (tiny) count output fences
        # the whole executable; chunk updates are sequentially dependent,
        # so no cross-chunk pipelining is lost, and the reader thread still
        # overlaps the next read with this compute.
        with obs.span("fit.chunk.wait", rows=m):
            jax.block_until_ready(self._stats.count)

    def finalize(self) -> FoldStats:
        if self._stats is None or self._offset != self.row_stop:
            raise ValueError(
                f"saw rows [{self.row_start}, {self._offset}), expected the "
                f"full window [{self.row_start}, {self.row_stop})")
        return self._stats


def compute_chunked(chunks: Iterable[tuple[jax.Array, jax.Array]],
                    n_total: int, n_folds: int, *,
                    chunk_rows: int | None = None,
                    use_pallas: bool = False) -> FoldStats:
    """One-call streaming accumulation over ``(X_chunk, Y_chunk)`` batches.

    ``chunk_rows`` pins the fixed shape of the compiled masked update up
    front (one trace for the whole stream); omitted, it is inferred from
    the first chunk.  ``use_pallas`` routes the heavy [G|C] contribution
    through the fused ``kernels.gram.xty_folds_masked`` tier.  Iterators
    with a ``close`` method (the prefetching store reader) are closed on
    every exit path.
    """
    acc = FoldStatsAccumulator(n_total, n_folds, chunk_rows=chunk_rows,
                               use_pallas=use_pallas)
    # Recompile sentinel: one fixed shape → at most one fresh trace for
    # the whole stream (zero when the signature is already warm).
    with _FIXED_UPDATE.compiles.expect(at_most=1):
        try:
            for X_chunk, Y_chunk in chunks:
                with obs.span("fit.foldstats.chunk_update",
                              rows=int(X_chunk.shape[0])):
                    acc.update(X_chunk, Y_chunk)
        finally:
            if hasattr(chunks, "close"):
                chunks.close()
    return acc.finalize()


@jax.jit
def _combine_pair(a: FoldStats, b: FoldStats) -> FoldStats:
    """Chan et al. pairwise combination of two per-fold partials.

    ``G``/``C``/``xsum``/``ysum``/``count`` are plain sums over disjoint row
    sets; the centred second moment needs the pairwise update
    ``M2_{a∪b} = M2_a + M2_b + (μ_a − μ_b)²·n_a n_b/(n_a+n_b)`` per fold —
    exact, and free of the ``Σy² − mȳ²`` cancellation (the reason
    ``FoldStats.ysq`` is stored centred at all).
    """
    n_a = a.count[:, None]                                   # (k, 1)
    n_b = b.count[:, None]
    mu_a = a.ysum / jnp.maximum(n_a, 1.0)
    mu_b = b.ysum / jnp.maximum(n_b, 1.0)
    both = (n_a > 0) & (n_b > 0)
    delta2 = jnp.where(both, (mu_a - mu_b) ** 2, 0.0)
    ysq = a.ysq + b.ysq + delta2 * n_a * n_b / jnp.maximum(n_a + n_b, 1.0)
    return FoldStats(G=a.G + b.G, C=a.C + b.C, xsum=a.xsum + b.xsum,
                     ysum=a.ysum + b.ysum, ysq=ysq, count=a.count + b.count)


def combine(parts: Sequence[FoldStats]) -> FoldStats:
    """Merge per-shard partial ``FoldStats`` into the global statistics.

    Pairwise (tree) reduction: exact for the summed statistics and applies
    the Chan update to the centred moments at every merge, so the result is
    invariant (to f32 rounding) under how the rows were split into shards.
    """
    if not parts:
        raise ValueError("combine() needs at least one partial FoldStats")
    parts = list(parts)
    while len(parts) > 1:
        merged = [_combine_pair(parts[i], parts[i + 1])
                  for i in range(0, len(parts) - 1, 2)]
        if len(parts) % 2:
            merged.append(parts[-1])
        parts = merged
    return parts[0]


def shard_row_ranges(n_total: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous near-equal row windows, one per shard (``data_axis``).

    Same size policy as ``fold_bounds`` (first ``n % s`` shards get the
    extra row) — but the two splits are independent: shard windows may cut
    folds anywhere, ``combine`` reconciles the partials.
    """
    if not 1 <= n_shards <= n_total:
        raise ValueError(f"need 1 <= n_shards <= n_total, got "
                         f"n_shards={n_shards}, n={n_total}")
    return fold_bounds(n_total, n_shards)


def compute_sharded_chunked(
        shard_streams: Sequence[Iterable[tuple[jax.Array, jax.Array]]],
        n_total: int, n_folds: int, *, mesh=None,
        data_axis: str = "data",
        chunk_rows: int | None = None,
        use_pallas: bool = False) -> FoldStats:
    """Sharded out-of-core accumulation along ``data_axis``.

    ``shard_streams[s]`` yields shard ``s``'s row chunks, covering exactly
    the window ``shard_row_ranges(n_total, len(shard_streams))[s]`` in
    global row order.  Each shard accumulates its own partial ``FoldStats``
    (``FoldStatsAccumulator`` with the shard's row window — the streaming
    mirror of ``partial_fold_stats``'s masked accumulation inside B-MOR's
    ``shard_map``); the finalize step then combines the partials:

    * the heavy ``(k, p, p+t)`` stacks ``[G | C]`` merge in a SINGLE
      ``psum`` over ``data_axis`` when a ``mesh`` is given (one collective
      for all folds, the same economy ``bmor.bmor_fit`` gets from the
      stacked layout), or a host-side tree reduction otherwise;
    * the small centred moment statistics merge with the Chan pairwise
      update (``combine``), which a plain ``psum`` cannot express.

    ``chunk_rows`` pins the fixed shape of the compiled masked update so
    EVERY shard's stream shares one program signature (one trace total,
    however the shard windows cut the folds).  Streams are consumed
    sequentially and closed (prefetching readers stop their thread and
    release their staging buffers as soon as their shard is done).  With
    a ``mesh``, shard ``s`` accumulates on the mesh's ``s``-th device, so
    each chip holds one partial ``(k, p, p+t)`` and the psum reads them
    where they are.
    """
    ranges = shard_row_ranges(n_total, len(shard_streams))
    if mesh is not None and mesh.size != len(shard_streams):
        raise ValueError(
            f"mesh has {mesh.size} devices but {len(shard_streams)} shard "
            f"streams were given — one row shard per device")
    devices = (list(mesh.devices.flat) if mesh is not None
               else [None] * len(shard_streams))
    parts: list[FoldStats] = []
    # Sentinel window: with chunk_rows pinned every shard shares ONE
    # program signature, so the whole sharded pass compiles at most once.
    # Left to infer (chunk_rows=None), ragged shard windows may pin
    # different first-chunk shapes per shard — allow one trace per shard.
    with _FIXED_UPDATE.compiles.expect(
            at_most=1 if chunk_rows else len(shard_streams)):
        for s, ((lo, hi), stream) in enumerate(zip(ranges, shard_streams)):
            acc = FoldStatsAccumulator(n_total, n_folds, row_start=lo,
                                       row_stop=hi, chunk_rows=chunk_rows,
                                       use_pallas=use_pallas,
                                       device=devices[s])
            with obs.span("fit.foldstats.shard", shard=s, row_lo=lo,
                          row_hi=hi):
                try:
                    for X_chunk, Y_chunk in stream:
                        acc.update(X_chunk, Y_chunk)
                finally:
                    if hasattr(stream, "close"):
                        stream.close()
            parts.append(acc.finalize())
            for dev in parts[-1].G.devices():
                obs.get_metrics().counter("shard_partials",
                                          device=dev.id).inc()
    if mesh is None or len(parts) == 1:
        return combine(parts)
    # Device-mesh finalize: the heavy (k, p, ·) partials, each already on
    # its own device, reduce in ONE psum over data_axis; only the (k, t)-
    # sized moment statistics go through the Chan merge (stripped of their
    # G/C so the big tensors are reduced exactly once).  The result is
    # replicated over the mesh.
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.compat import shard_map

    if mesh.shape[data_axis] != len(parts):
        raise ValueError(
            f"mesh axis {data_axis!r} has {mesh.shape[data_axis]} shards "
            f"but {len(parts)} shard streams were accumulated")
    merged = combine([jax.device_put(
        dataclasses.replace(s, G=s.G[:, :0, :0], C=s.C[:, :0, :0]),
        devices[0]) for s in parts])
    by_shard = NamedSharding(mesh, P(data_axis))

    def global_stack(leaves):
        # The shards' (k, ·, ·) blocks, concatenated along the fold axis
        # in place: shard s's block is device s's buffer, never copied.
        shape = (len(leaves) * leaves[0].shape[0],) + leaves[0].shape[1:]
        return jax.make_array_from_single_device_arrays(shape, by_shard,
                                                        leaves)

    G, C = jax.jit(shard_map(
        lambda g, c: jax.lax.psum((g, c), data_axis), mesh=mesh,
        in_specs=(P(data_axis), P(data_axis)), out_specs=(P(), P()),
        check_vma=False))(global_stack([s.G for s in parts]),
                          global_stack([s.C for s in parts]))
    merged = jax.device_put(merged, NamedSharding(mesh, P()))
    return dataclasses.replace(merged, G=G, C=C)


class ColumnMoments:
    """Streaming per-column mean/variance over row chunks (Chan/Welford).

    The first pass of the two-pass streaming standardization
    (``pipeline.fit_chunked``): accumulates ``(count, mean, M2)`` per
    column in float64 on the host — the chunks are memmap views, so this
    pass costs one read of the rows and O(columns) residency.
    """

    def __init__(self) -> None:
        self.count = 0.0
        self.mean: "np.ndarray | None" = None
        self.m2: "np.ndarray | None" = None

    def update(self, A) -> None:
        import numpy as np
        A = np.asarray(A, np.float64)
        n_b = float(A.shape[0])
        if n_b == 0:
            return
        mu_b = A.mean(axis=0)
        m2_b = ((A - mu_b) ** 2).sum(axis=0)
        if self.mean is None:
            self.count, self.mean, self.m2 = n_b, mu_b, m2_b
            return
        n_a = self.count
        delta = mu_b - self.mean
        tot = n_a + n_b
        self.mean = self.mean + delta * (n_b / tot)
        self.m2 = self.m2 + m2_b + delta ** 2 * (n_a * n_b / tot)
        self.count = tot

    def std(self, eps: float = 1e-6) -> "np.ndarray":
        import numpy as np
        assert self.mean is not None, "no rows seen"
        return np.sqrt(self.m2 / self.count) + eps


def validation_scores_per_target(
        stats: FoldStats, f: int, Q: jax.Array, evals: jax.Array,
        C_tr: jax.Array, lambdas: jax.Array, scoring: str) -> jax.Array:
    """Per-λ, per-TARGET validation score of split ``f``, shape ``(r, t)``.

    The un-averaged form of ``validation_scores_from_stats`` (which is its
    mean over targets) — the column-blocked driver (``repro.wholebrain``)
    needs the per-column scores so it can aggregate across target blocks
    on the host without ever building a full-``t`` score tensor in one
    program.  Every contraction is per-column independent, so a column
    block of this function's output is bit-identical to the same columns
    of the full-width call (the property the target-block invariance
    harness locks down).

    With ``W_r = Q (Λ+λ_r)⁻¹ QᵀC_tr``, the held-out error needs only the
    fold's own statistics — no validation rows:

        Σŷ   = xsum_fᵀ W_r          Σŷ²  = diag(W_rᵀ G_f W_r)
        Σyŷ  = diag(C_fᵀ W_r)       ȳ, Σ(y−ȳ)², m  from the moment stats.

    Everything stays in the eigenbasis, so the per-λ work is diagonal plus
    one ``(p×p)·(p×t)`` contraction per λ — the mutualisation of Eq. 5
    extended to the scoring itself.  ``"r2"`` and ``"r"`` match
    ``ridge._score`` exactly in exact arithmetic (after the mean the
    wrapper below takes).

    Precision caveat: unlike the row-based CV loop (which centres the
    validation rows before any large contraction), statistics can only be
    centred *after* rotation, so f32 accuracy degrades roughly
    quadratically in ``|ȳ|/σ_y``.  λ selection stays robust for "r2"
    (score gaps between λ grow with the mean via the shrinkage penalty),
    but extreme un-standardized targets should be standardized first —
    ``BrainEncoder.fit_chunks`` enforces this.
    """
    # Coefficients in the eigenbasis, per λ: Z_r = (Λ+λ_r)⁻¹ QᵀC_tr.
    A = jnp.matmul(Q.T, C_tr, preferred_element_type=jnp.float32)  # (p, t)
    Z = A[None] / (evals[None, :, None] + lambdas[:, None, None])  # (r, p, t)
    m = stats.count[f]
    mu = (stats.ysum[f] / m)[None]                                 # (1, t) ȳ
    m2 = stats.ysq[f][None]                                        # Σ(y−ȳ)²
    # Rotate this fold's validation statistics into the eigenbasis, in
    # CENTRED form: Ghat_c/Chat_c are the rotations of Σ(x−x̄)(x−x̄)ᵀ and
    # Σ(x−x̄)(y−ȳ)ᵀ, so every per-λ contraction below runs at signal
    # scale — the raw-moment expansions (s_hat2 − mŷ̄², …) would cancel
    # catastrophically in f32 when predictions inherit large target means
    # (the regime FoldStats.ysq is centred for).
    u = jnp.matmul(stats.xsum[f], Q,
                   preferred_element_type=jnp.float32)             # (p,)
    Chat = jnp.matmul(Q.T, stats.C[f],
                      preferred_element_type=jnp.float32)          # (p, t)
    Chat_c = Chat - u[:, None] * mu
    Ghat = jnp.matmul(Q.T, jnp.matmul(stats.G[f], Q,
                                      preferred_element_type=jnp.float32),
                      preferred_element_type=jnp.float32)          # (p, p)
    Ghat_c = Ghat - u[:, None] * u[None, :] / m
    s_hat = jnp.einsum("p,rpt->rt", u, Z,
                       preferred_element_type=jnp.float32)         # Σŷ
    c_xy = jnp.einsum("pt,rpt->rt", Chat_c, Z,
                      preferred_element_type=jnp.float32)          # Σ(y−ȳ)ŷ
    c_p2 = jnp.einsum("rpt,pq,rqt->rt", Z, Ghat_c, Z,
                      preferred_element_type=jnp.float32)          # Σ(ŷ−ŷ̄)²
    return _scores_from_moments(m, mu, m2, s_hat, c_xy, c_p2, scoring)


def _scores_from_moments(m, mu, m2, s_hat, c_xy, c_p2, scoring: str):
    """Per-λ, per-target score from the held-out moments: ``m`` rows,
    target mean ``mu`` and ``m2 = Σ(y−ȳ)²``, prediction sum ``s_hat``,
    ``c_xy = Σ(y−ȳ)ŷ`` and ``c_p2 = Σ(ŷ−ŷ̄)²``."""
    if scoring == "r2":
        # Σ(y−ŷ)² = Σ(y−ȳ)² − 2Σ(y−ȳ)(ŷ−ŷ̄) + Σ(ŷ−ŷ̄)² + m(ŷ̄−ȳ)²,
        # with only the scalar fold means meeting at full magnitude.
        mean_term = m * (s_hat / m - mu) ** 2
        ss_res = m2 - 2.0 * c_xy + c_p2 + mean_term
        return 1.0 - ss_res / (m2 + 1e-12)
    # Pearson r from centred moments per target.
    den = jnp.sqrt(jnp.maximum(m2 * c_p2, 0.0)) + 1e-12
    return c_xy / den


def validation_scores_from_weights(stats: FoldStats, f: int, Ws: jax.Array,
                                   scoring: str) -> jax.Array:
    """Per-λ, per-target validation score of split ``f`` for explicit
    training weights ``Ws`` (r, p, t) — ``validation_scores_per_target``
    without an eigenbasis: the fold's centred statistics contract with
    each ``W_r`` directly (``Σŷ = x̄ᵀW``, ``Σ(y−ȳ)ŷ = diag(C_cᵀW)``,
    ``Σ(ŷ−ŷ̄)² = diag(WᵀG_cW)``), at the fit's matmul precision."""
    m = stats.count[f]
    mu = (stats.ysum[f] / m)[None]                                 # (1, t)
    m2 = stats.ysq[f][None]
    u = stats.xsum[f]                                              # (p,)
    G_c = stats.G[f] - u[:, None] * u[None, :] / m
    C_c = stats.C[f] - u[:, None] * mu
    s_hat = jnp.einsum("p,rpt->rt", u, Ws, precision=PRECISION)
    c_xy = jnp.einsum("pt,rpt->rt", C_c, Ws, precision=PRECISION)
    GW = jnp.einsum("pq,rqt->rpt", G_c, Ws, precision=PRECISION)
    c_p2 = jnp.sum(Ws * GW, axis=1)
    return _scores_from_moments(m, mu, m2, s_hat, c_xy, c_p2, scoring)


def validation_scores_from_stats(
        stats: FoldStats, f: int, Q: jax.Array, evals: jax.Array,
        C_tr: jax.Array, lambdas: jax.Array, scoring: str) -> jax.Array:
    """Per-λ validation score of split ``f`` from sufficient statistics —
    the mean over targets of ``validation_scores_per_target``, shape
    ``(r,)``.  See that function for the algebra and the precision caveat;
    ``"r2"`` and ``"r"`` match ``ridge._score`` exactly in exact
    arithmetic."""
    return jnp.mean(validation_scores_per_target(
        stats, f, Q, evals, C_tr, lambdas, scoring), axis=1)


__all__: Sequence[str] = (
    "ColumnMoments", "FoldStats", "FoldStatsAccumulator",
    "chunk_update_compile_count", "chunk_update_compiles", "combine",
    "compute", "compute_chunked",
    "compute_sharded_chunked", "fold_bounds", "fold_of_rows",
    "partial_fold_stats", "shard_row_ranges", "validation_scores_from_stats",
    "validation_scores_from_weights", "validation_scores_per_target",
)
