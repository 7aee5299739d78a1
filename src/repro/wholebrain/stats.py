"""Column-blocked fold statistics — the target-axis streaming tier.

The row-streaming tier (``foldstats.FoldStatsAccumulator``) bounds memory
in ``n`` but still materialises the full ``(k, p, t)`` cross-covariance
``C`` — at the paper's whole-brain scale (Table 1: t≈264k targets) that
single tensor is the object that no longer fits.  This module blocks the
TARGET axis the same way the row tier blocks rows:

* the shared statistics (``G`` (k, p, p), ``xsum``, ``count``) depend only
  on ``X`` and are accumulated ONCE, by the existing fixed-shape masked
  update fed zero-width ``Y`` chunks (``RunStore.iter_chunks(col_range=
  (0, 0))``);
* the per-target statistics (``C`` (k, p, t_block), ``ysum``, ``ysq``)
  are accumulated per column block by ``ColumnBlockAccumulator`` — one
  streaming pass over the rows per block, touching only that block's
  ``Y`` column window (a strided mmap view, so only its pages fault in).

Peak memory is ``O(p² + p·t_block)`` — independent of ``t``.

Bit-identity contract (what ``tests/test_wholebrain.py`` locks down):
every contraction here is per-target-column independent, and on the CPU
backend XLA's column-blocked GEMMs are bitwise equal to the same columns
of the full-width GEMM for block widths ≥ 2 (width-1 lowers to a gemv
with a different reduction order).  All block computations therefore run
at ONE fixed padded width ``t_pad`` (the ragged last block is zero-padded
and sliced after), which simultaneously keeps the compiled update at a
single trace across every block — the same fixed-shape contract as the
row tier, extended to the target axis.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import foldstats
from repro.kernels.gram import PRECISION


def column_blocks(t: int, t_block: int) -> list[tuple[int, int]]:
    """Contiguous target-column windows of width ``t_block`` (ragged tail).

    ``t_block >= 2`` unless it covers everything: a width-1 block would
    lower the per-block GEMMs to gemv, whose reduction order breaks the
    bitwise column-slice identity the invariance harness gates (only the
    padded LAST block may be narrower than 2 real columns — its compute
    still runs at the fixed padded width).
    """
    if t < 1:
        raise ValueError(f"need t >= 1, got t={t}")
    if t_block < 2 and t_block < t:
        raise ValueError(
            f"t_block must be >= 2 (width-1 GEMMs are gemv and break the "
            f"bitwise column-slice identity), got t_block={t_block}")
    t_block = min(t_block, t)
    return [(lo, min(lo + t_block, t)) for lo in range(0, t, t_block)]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ColumnBlockStats:
    """Per-fold sufficient statistics of ONE target-column window.

    The target-dependent half of ``foldstats.FoldStats`` — grafted onto
    the shared ``G``/``xsum``/``count`` of the X-only pass, the pair is
    indistinguishable from a full ``FoldStats`` restricted to the block's
    columns (bit-for-bit, see the module docstring).
    """

    C: jax.Array        # (k, p, t_pad)  per-fold XᵀY over the window
    ysum: jax.Array     # (k, t_pad)     per-fold Σ y
    ysq: jax.Array      # (k, t_pad)     per-fold centred Σ (y − ȳ_f)²
    count: jax.Array    # (k,)           per-fold row count

    @property
    def C_total(self) -> jax.Array:
        return jnp.sum(self.C, axis=0)


class _ColumnBlockUpdate:
    """The ONE compiled program of the per-block accumulation.

    The target-block mirror of ``foldstats._FixedShapeUpdate``: same
    masked slot layout, same Chan centred-moment update, but WITHOUT the
    ``G``/``xsum`` terms — those are shared across blocks and recomputing
    the ``O(np²)`` Gram once per block would multiply the dominant cost by
    the block count.  The ``C`` einsum is the exact column sub-problem of
    the fused ``Xᵀ[X | Y]`` update, so its output is bitwise equal to the
    corresponding columns of the full-width accumulation.
    """

    def __init__(self) -> None:
        self.compiles = obs.CompileCounter("wholebrain.colblock_update")
        # The running statistics are donated: the update writes them in
        # place, so the device holds one copy of them, not two.
        self._fn = jax.jit(self._update, static_argnames=("use_pallas",),
                           donate_argnums=0)

    @property
    def compile_count(self) -> int:
        return self.compiles.count

    def __call__(self, stats: ColumnBlockStats, X, Y, onehot, slot_fold, *,
                 use_pallas: bool = False) -> ColumnBlockStats:
        return self._fn(stats, X, Y, onehot, slot_fold,
                        use_pallas=use_pallas)

    def _update(self, stats: ColumnBlockStats, X: jax.Array, Y: jax.Array,
                onehot: jax.Array, slot_fold: jax.Array,
                use_pallas: bool = False) -> ColumnBlockStats:
        # Python side effect at TRACE time only — the compile counter the
        # wholebrain CI lane gates at exactly 1 across ALL blocks (shared
        # obs.CompileCounter; expect() windows arm the strict sentinel).
        self.compiles.mark()
        dt = jnp.promote_types(X.dtype, Y.dtype)
        w = onehot                                          # (m, s) f32 0/1
        if use_pallas:
            # Same fused masked kernel as the row tier, with Z = the
            # block's Y columns only (the X half of [G|C] is shared across
            # blocks and accumulated once, in the X-only/first-block pass).
            from repro.kernels import ops
            Cb = ops.xty_folds_masked(X.astype(dt), Y.astype(dt),
                                      w.astype(dt))          # (s, p, t_pad)
        else:
            Xw = (X.astype(dt)[None]
                  * jnp.swapaxes(w, 0, 1)[:, :, None].astype(dt))
            Cb = jnp.einsum("smp,mq->spq", Xw, Y.astype(dt),
                            precision=PRECISION,
                            preferred_element_type=jnp.float32)
        Yf = Y.astype(jnp.float32)
        cnt = jnp.sum(w, axis=0)                             # (s,)
        ysum = jnp.einsum("ms,mt->st", w, Yf, precision=PRECISION,
                          preferred_element_type=jnp.float32)
        # Chan pairwise combination, identical to the row tier's — every
        # term is per-column independent, so the block is a bitwise column
        # slice of the full-width moment statistics.
        mu_b = ysum / jnp.maximum(cnt, 1.0)[:, None]
        d = Yf[None, :, :] - mu_b[:, None, :]                # (s, m, t_pad)
        m2 = jnp.einsum("ms,smt->st", w, d * d, precision=PRECISION,
                        preferred_element_type=jnp.float32)
        n_a = stats.count[slot_fold]                         # (s,)
        mu_a = stats.ysum[slot_fold] / jnp.maximum(n_a, 1.0)[:, None]
        both = ((n_a > 0) & (cnt > 0))[:, None]
        delta2 = jnp.where(both, (mu_a - mu_b) ** 2, 0.0)
        ysq_add = m2 + delta2 * (n_a * cnt
                                 / jnp.maximum(n_a + cnt, 1.0))[:, None]
        return ColumnBlockStats(
            C=stats.C.at[slot_fold].add(Cb),
            ysum=stats.ysum.at[slot_fold].add(ysum),
            ysq=stats.ysq.at[slot_fold].add(ysq_add),
            count=stats.count.at[slot_fold].add(cnt))


# Module-level singleton: every block of every stream shares one jit
# cache, so a whole-brain sweep of hundreds of blocks costs ONE trace.
_COLBLOCK_UPDATE = _ColumnBlockUpdate()


def colblock_update_compile_count() -> int:
    """Trace count of the column-block update (monotonic, process-wide).

    Take a delta around a blocked fit to measure its compiles; the
    contract is ``delta == 1`` for a fresh ``(chunk_rows, p, t_pad, k)``
    signature however many blocks are streamed, and ``0`` for a repeat.

    (Thin alias over ``colblock_update_compiles().count`` — the shared
    ``obs.CompileCounter`` primitive.)
    """
    return _COLBLOCK_UPDATE.compiles.count


def colblock_update_compiles() -> "obs.CompileCounter":
    """The column-block update's :class:`repro.obs.CompileCounter`
    (``expect()`` windows arm the strict recompile sentinel)."""
    return _COLBLOCK_UPDATE.compiles


class ColumnBlockAccumulator(foldstats.FoldStatsAccumulator):
    """Streaming builder of ``ColumnBlockStats`` for one column window.

    Reuses ALL of the row tier's machinery — chunk splitting, zero-row
    padding, slot masks, offset accounting, the finalize contract — and
    replaces only the applied statistic (the ``_apply`` seam): incoming
    ``Y`` chunks carry the block's real columns and are zero-padded on the
    COLUMN axis to the fixed ``t_pad``, so every block of every width
    presents the same shape to the one compiled update.  Padded columns
    accumulate exact zeros and are sliced away by the solver.
    """

    def __init__(self, n_total: int, n_folds: int, t_pad: int, *,
                 row_start: int = 0, row_stop: int | None = None,
                 chunk_rows: int | None = None,
                 use_pallas: bool = False):
        if t_pad < 1:
            raise ValueError(f"t_pad must be >= 1, got {t_pad}")
        super().__init__(n_total, n_folds, row_start=row_start,
                         row_stop=row_stop, chunk_rows=chunk_rows,
                         use_pallas=use_pallas)
        self.t_pad = t_pad

    def _init_stats(self, p: int, t: int) -> ColumnBlockStats:
        if t > self.t_pad:
            raise ValueError(f"chunk has {t} target columns but the fixed "
                             f"block width is t_pad={self.t_pad}")
        k = len(self.bounds)
        z = jnp.zeros
        return ColumnBlockStats(C=z((k, p, self.t_pad), jnp.float32),
                                ysum=z((k, self.t_pad), jnp.float32),
                                ysq=z((k, self.t_pad), jnp.float32),
                                count=z((k,), jnp.float32))

    def _apply(self, Xs, Ys, onehot, slot_fold) -> None:
        import numpy as np
        Ys = np.asarray(Ys)
        if Ys.shape[1] < self.t_pad:       # ragged block: zero-pad columns
            Yp = np.zeros((Ys.shape[0], self.t_pad), Ys.dtype)
            Yp[:, :Ys.shape[1]] = Ys
            Ys = Yp
        X, Y = foldstats.send_chunk(jnp.asarray, Xs, Ys, onehot, slot_fold)
        self._stats = _COLBLOCK_UPDATE(self._stats, X, Y, onehot, slot_fold,
                                       use_pallas=self.use_pallas)


__all__ = ["ColumnBlockAccumulator", "ColumnBlockStats", "column_blocks",
           "colblock_update_compile_count", "colblock_update_compiles"]
