"""SVD/eigh-mutualised multi-target RidgeCV (paper §2.3.1, §3).

This module is the single-shard building block of the paper's pipeline: the
scikit-learn-style ridge regression whose factorisation is computed *once*
and reused across all targets and all candidate regularisation strengths.

Two algebraically equivalent factorisation paths are provided:

* ``eigh`` (primal, used when ``n >= p``): eigendecompose the Gram matrix
  ``G = XᵀX = Q Λ Qᵀ``.  Then ``M(λ) Y = Q (Λ+λI)⁻¹ Qᵀ (XᵀY)``.  The
  eigenvalues of ``G`` are the squared singular values of ``X``, so the λ
  sweep is the same diagonal rescale as scikit-learn's SVD path (Eq. 5 of the
  paper) — but ``G`` and ``XᵀY`` are *sums over rows* of ``X``/``Y``, which is
  what makes the distributed (B-MOR) version a single ``psum`` (see
  ``repro.core.bmor``).
* ``dual`` (kernel, used when ``n < p``): eigendecompose ``K = XXᵀ = P Γ Pᵀ``;
  dual coefficients ``α(λ) = P (Γ+λI)⁻¹ Pᵀ Y`` and ``W = Xᵀ α``.

Both keep the per-λ work diagonal: ``O(p)`` (or ``O(n)``) scaling per λ, as
in the paper's complexity analysis ``T_M = O(p² n r + p r)``.

Cross-validation (``ridge_cv``) runs on single-pass fold statistics
(``repro.core.foldstats``): per-fold partials ``{G_f, C_f}`` are accumulated
once and every training split derives by the Gram downdate
``G_train(f) = G_total − G_f``.  **Algorithm-1 fidelity note:** downdating is
algebraically *exact*, not an approximation — ``XᵀX`` is a sum over rows, so
subtracting a fold's partial sum reproduces ``X_trᵀX_tr`` identically (up to
f32 rounding in the accumulation order); every split still pays its own
``eigh``, exactly the per-split ``svd(X_train)`` of paper Algorithm 1.  The
dual mirror slices per-fold kernel blocks ``K[tr, tr]`` out of one ``XXᵀ``.
The seed per-fold re-accumulation is kept as ``ridge_cv_reference`` for
parity tests and the ``benchmarks/foldstats_bench.py`` trajectory.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import foldstats, spd

# The paper's λ grid (§2.2.4).
PAPER_LAMBDA_GRID: tuple[float, ...] = (
    0.1, 1.0, 100.0, 200.0, 300.0, 400.0, 600.0, 800.0, 900.0, 1000.0, 1200.0
)


@dataclasses.dataclass(frozen=True)
class RidgeCVConfig:
    """Configuration of the multi-target cross-validated ridge solve."""

    lambdas: tuple[float, ...] = PAPER_LAMBDA_GRID
    n_folds: int = 5
    method: Literal["auto", "eigh", "dual"] = "auto"
    # Small diagonal jitter added to the Gram matrix before eigh for numerical
    # stability in float32 (the paper runs float64 CPU BLAS; see DESIGN §2).
    jitter: float = 1e-6
    # Score used to select λ across folds: Pearson correlation ("r") matches
    # the paper's reported metric; "r2" is the classical ridge CV score.
    scoring: Literal["r", "r2"] = "r2"
    # Route the Gram/cross-covariance accumulations (fold statistics, dual
    # kernel, Xᵀα) through the Pallas TPU kernels (repro.kernels).  Off by
    # default: on CPU the kernels run in interpret mode (correct but slow);
    # on TPU this is the "better BLAS" lever of paper §4.3.
    use_pallas: bool = False

    def resolve_method(self, n: int, p: int) -> str:
        if self.method != "auto":
            return self.method
        return "eigh" if n >= p else "dual"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RidgeFactors:
    """Reusable factorisation of the feature matrix.

    ``basis`` is ``Q`` (p×p, primal) or ``P`` (n×n, dual); ``evals`` are the
    eigenvalues of the corresponding Gram/kernel matrix, i.e. the squared
    singular values of ``X``.  ``M(λ)`` never needs to be materialised: the λ
    sweep only rescales coordinates in the eigenbasis.
    """

    basis: jax.Array        # (p,p) primal | (n,n) dual
    evals: jax.Array        # (p,) | (n,)
    primal: bool

    def tree_flatten(self):
        return (self.basis, self.evals), self.primal

    @classmethod
    def tree_unflatten(cls, aux, children):
        basis, evals = children
        return cls(basis=basis, evals=evals, primal=aux)


def gram(X: jax.Array) -> jax.Array:
    """``XᵀX`` with f32 accumulation (MXU-friendly)."""
    return jnp.matmul(X.T, X, preferred_element_type=jnp.float32)


def gram_xty(X: jax.Array, Y: jax.Array, *,
             use_pallas: bool = False) -> jax.Array:
    """``XᵀY`` with f32 accumulation (Pallas-routable)."""
    if use_pallas:
        from repro.kernels import ops
        return ops.xty(X, Y)
    return jnp.matmul(X.T, Y, preferred_element_type=jnp.float32)


def xxt(X: jax.Array, *, use_pallas: bool = False) -> jax.Array:
    """``XXᵀ`` (the dual-path kernel matrix) with f32 accumulation.

    The Pallas route reuses the tiled cross-Gram kernel on ``Xᵀ``:
    ``(Xᵀ)ᵀ(Xᵀ) = XXᵀ``.
    """
    if use_pallas:
        from repro.kernels import ops
        Xt = X.T
        return ops.xty(Xt, Xt)
    return jnp.matmul(X, X.T, preferred_element_type=jnp.float32)


def eigh(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(evals, basis)`` of a symmetric matrix, eigenvalues ascending.

    ``jnp.linalg.eigh``, except that on TPU it lowers to the Jacobi custom
    call.  JAX's default TPU eigh (QDWH divide and conquer) emits code that
    grows with the width: compiled for v5e it is 230 MB of code at
    p = 1,024 (84 s to compile) and 615 MB at p = 2,048 (257 s), and at the
    paper's p = 16,384 it had not compiled after 10 minutes and 16 GB of
    host memory.  The Jacobi call compiles in seconds at any width, but
    runs 2.7 s at p = 2,048 on a v5e chip and grows as p³ — which is why
    the streamed CV factorises per λ on TPU instead (``core.spd``).
    """
    from repro.kernels import ops
    if not ops.on_tpu():
        return tuple(jnp.linalg.eigh(a))
    basis, evals = jax.lax.linalg.eigh(
        a, implementation=jax.lax.linalg.EighImplementation.JACOBI)
    return evals, basis


def factorize(X: jax.Array, cfg: RidgeCVConfig) -> RidgeFactors:
    """Factorise ``X`` once; reused for every λ and every target (Eq. 4-5)."""
    n, p = X.shape
    method = cfg.resolve_method(n, p)
    if method == "eigh":
        if cfg.use_pallas:
            from repro.kernels import ops
            gram_fn = ops.gram
        else:
            gram_fn = gram
        G = gram_fn(X) + cfg.jitter * jnp.eye(p, dtype=jnp.float32)
        evals, Q = eigh(G)
        return RidgeFactors(basis=Q, evals=evals, primal=True)
    K = xxt(X, use_pallas=cfg.use_pallas)
    K = K + cfg.jitter * jnp.eye(n, dtype=jnp.float32)
    evals, P = eigh(K)
    return RidgeFactors(basis=P, evals=evals, primal=False)


def solve(factors: RidgeFactors, XtY_or_Y: jax.Array, lam: jax.Array,
          X: jax.Array | None = None, use_pallas: bool = False) -> jax.Array:
    """Apply ``M(λ)`` to the targets through the shared factorisation.

    Primal: pass ``XᵀY`` (p×t) → returns ``W = Q (Λ+λ)⁻¹ Qᵀ XᵀY`` (p×t).
    Dual:   pass ``Y`` (n×t) and ``X`` → ``W = Xᵀ α`` with dual coefficients
    ``α = P (Γ+λ)⁻¹ Pᵀ Y`` (the ``Xᵀα`` matmul is Pallas-routable).
    """
    B = factors.basis
    z = jnp.matmul(B.T, XtY_or_Y, preferred_element_type=jnp.float32)
    z = z / (factors.evals + lam)[:, None]
    out = jnp.matmul(B, z, preferred_element_type=jnp.float32)
    if factors.primal:
        return out
    assert X is not None, "dual solve needs X to map dual coeffs to weights"
    return gram_xty(X, out, use_pallas=use_pallas)


def solve_lambda_grid(factors: RidgeFactors, XtY_or_Y: jax.Array,
                      lambdas: Sequence[float],
                      X: jax.Array | None = None,
                      use_pallas: bool = False) -> jax.Array:
    """All-λ solve, stacked on a leading axis: (r, p, t).

    The rotation into the eigenbasis (``Qᵀ XᵀY``) is shared across the grid —
    this is exactly the mutualisation of paper Eq. 5, where only the diagonal
    ``(S²+λI)⁻¹`` depends on λ.
    """
    if use_pallas and factors.primal:
        from repro.kernels import ops
        a = jnp.matmul(factors.basis.T, XtY_or_Y,
                       preferred_element_type=jnp.float32)
        return ops.solve_lambda_grid(factors.basis, factors.evals, a,
                                     jnp.asarray(lambdas, jnp.float32))
    B = factors.basis
    z = jnp.matmul(B.T, XtY_or_Y, preferred_element_type=jnp.float32)
    lams = jnp.asarray(lambdas, dtype=z.dtype)                    # (r,)
    zs = z[None, :, :] / (factors.evals[None, :, None] + lams[:, None, None])
    out = jnp.einsum("ij,rjt->rit", B, zs,
                     preferred_element_type=jnp.float32)
    if factors.primal:
        return out
    assert X is not None
    if use_pallas:
        from repro.kernels import ops
        return jnp.stack([ops.xty(X, out[r]) for r in range(len(lambdas))])
    return jnp.einsum("ni,rnt->rit", X, out,
                      preferred_element_type=jnp.float32)


# Contiguous k-fold boundaries — canonical implementation lives in
# ``foldstats``; kept here under the historical name for existing callers
# (``banded.py``, ``bmor.bmor_fit_dual``, tests).
_fold_bounds = foldstats.fold_bounds


def _score(Y_true: jax.Array, Y_pred: jax.Array, kind: str) -> jax.Array:
    """Mean score across targets (higher is better)."""
    if kind == "r2":
        ss_res = jnp.sum((Y_true - Y_pred) ** 2, axis=0)
        ss_tot = jnp.sum((Y_true - jnp.mean(Y_true, axis=0)) ** 2, axis=0) + 1e-12
        return jnp.mean(1.0 - ss_res / ss_tot)
    yt = Y_true - jnp.mean(Y_true, axis=0)
    yp = Y_pred - jnp.mean(Y_pred, axis=0)
    num = jnp.sum(yt * yp, axis=0)
    den = jnp.sqrt(jnp.sum(yt ** 2, axis=0) * jnp.sum(yp ** 2, axis=0)) + 1e-12
    return jnp.mean(num / den)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RidgeCVResult:
    weights: jax.Array       # (p, t)
    best_lambda: jax.Array   # scalar
    best_index: jax.Array    # scalar int
    cv_scores: jax.Array     # (r,) mean validation score per λ


def _lambda_grid(cfg: RidgeCVConfig) -> jax.Array:
    # λ grid in f32 regardless of X.dtype: the whole solve accumulates in f32
    # (preferred_element_type), so bf16/f16 inputs must sweep — and select —
    # the identical grid, not a low-precision rounding of it.
    return jnp.asarray(cfg.lambdas, dtype=jnp.float32)


def _r2_scores_trace(Bv: jax.Array, A: jax.Array, Y_val: jax.Array,
                     evals: jax.Array, lams: jax.Array) -> jax.Array:
    """Mean-over-targets R² per λ without materialising predictions.

    With validation predictions ``P(λ) = Bv · diag(1/(Λ+λ)) · A`` the CV
    score ``mean_j (1 − ‖Y_j − P_j‖²/ss_tot_j)`` expands, via the centred
    decomposition ``‖Y_j − P_j‖² = ss_tot_j − 2⟨Y_j−ȳ_j, P_j−P̄_j⟩ +
    ‖P_j−P̄_j‖² + v(P̄_j−ȳ_j)²``, into λ-independent contractions plus a
    per-λ quadratic form in the diagonal:

        Σ_j ss_res_j/ss_tot_j = t₀ − 2·Dᵀε + Dᵀ(G_c ∘ S)D + v·Σ_j(P̄_j−ȳ_j)²/ss_tot_j

    (D = 1/(Λ+λ), ε = Σ_j A∘(BcᵀY_c)/ss_tot, S = A diag(1/ss_tot) Aᵀ,
    G_c = BcᵀBc with Bc the row-centred ``Bv``) — algebraically identical
    to scoring the r materialised prediction tensors but
    ``O(vpt + p²t + rpt + rp²)`` instead of ``O(r·v·p·t)``: the λ sweep
    stays diagonal even through the scoring, extending the Eq. 5
    mutualisation to the CV loop itself.  Every sum is over CENTRED
    quantities (only the per-target scalar fold means meet at full
    magnitude), so the f32 arithmetic stays stable for un-standardized
    large-mean targets — the ``Σy² − mȳ²`` raw-moment expansion would
    cancel catastrophically there (see ``foldstats.FoldStats.ysq``).
    """
    v, t = Y_val.shape
    Y32 = Y_val.astype(jnp.float32)
    mu = jnp.mean(Y32, axis=0)
    Yc = Y32 - mu
    inv = 1.0 / (jnp.sum(Yc ** 2, axis=0) + 1e-12)                 # 1/ss_tot
    t0 = jnp.sum(jnp.sum(Yc ** 2, axis=0) * inv)
    ub = jnp.mean(Bv, axis=0)                                      # (p,)
    Bc = Bv - ub                                                   # centred
    Mc = jnp.matmul(Bc.T, Yc, preferred_element_type=jnp.float32) * inv[None]
    eps = jnp.sum(A * Mc, axis=1)                                  # (p,)
    S = jnp.matmul(A * inv[None], A.T, preferred_element_type=jnp.float32)
    Gc = jnp.matmul(Bc.T, Bc, preferred_element_type=jnp.float32)
    F = Gc * S
    D = 1.0 / (evals[None, :] + lams[:, None])                     # (r, p)
    cross = D @ eps
    quad = jnp.einsum("rp,pq,rq->r", D, F, D,
                      preferred_element_type=jnp.float32)
    # Fold-mean predictions per λ: P̄(λ) = ubᵀ·diag(D)·A (r, t).
    pbar = jnp.einsum("p,rp,pt->rt", ub, D, A,
                      preferred_element_type=jnp.float32)
    mean_term = v * jnp.sum((pbar - mu[None]) ** 2 * inv[None], axis=1)
    return 1.0 - (t0 - 2.0 * cross + quad + mean_term) / t


def _fold_scores(Bv: jax.Array, A: jax.Array, Y_val: jax.Array,
                 evals: jax.Array, lams: jax.Array,
                 scoring: str) -> jax.Array:
    """Per-λ validation scores of one split, from eigenbasis factors.

    ``"r2"`` uses the trace identity above; ``"r"`` (per-target Pearson,
    nonlinear in the per-target moments) materialises the per-λ prediction
    tensor and scores it exactly like the seed path.
    """
    if scoring == "r2":
        return _r2_scores_trace(Bv, A, Y_val, evals, lams)
    Bs = Bv[None] / (evals[None, None, :] + lams[:, None, None])   # (r, v, p)
    preds = jnp.matmul(Bs, A[None], preferred_element_type=jnp.float32)
    return jax.vmap(lambda Yp: _score(Y_val, Yp, scoring))(preds)


def _ridge_cv_primal(X: jax.Array, Y: jax.Array,
                     cfg: RidgeCVConfig) -> RidgeCVResult:
    """Primal CV on downdated fold statistics — one Gram pass total.

    Per split: ``eigh(G_total − G_f)`` (Algorithm 1's per-split
    factorisation), validation predictions straight from the eigenbasis
    (``X_val Q · (Λ+λ)⁻¹ · Qᵀ C_tr``) so no per-λ weight matrix is ever
    materialised during CV, and the refit reuses ``G_total``/``C_total`` —
    the fold partials already sum to the full-data statistics.
    """
    n, p = X.shape
    bounds = foldstats.fold_bounds(n, cfg.n_folds)
    stats = foldstats.compute(X, Y, cfg.n_folds, use_pallas=cfg.use_pallas)
    eye = cfg.jitter * jnp.eye(p, dtype=jnp.float32)
    lams = _lambda_grid(cfg)
    per_lambda_scores = []
    for f, (lo, hi) in enumerate(bounds):
        G_tr, C_tr = stats.train(f)                   # Gram downdate (exact)
        evals, Q = eigh(G_tr + eye)                   # per-split eigh
        A = jnp.matmul(Q.T, C_tr, preferred_element_type=jnp.float32)
        Bv = jnp.matmul(X[lo:hi], Q, preferred_element_type=jnp.float32)
        per_lambda_scores.append(
            _fold_scores(Bv, A, Y[lo:hi], evals, lams, cfg.scoring))
    cv_scores = jnp.mean(jnp.stack(per_lambda_scores), axis=0)    # (r,)
    best = jnp.argmax(cv_scores)
    # Refit on the full data: the summed fold statistics ARE the full-data
    # Gram/cross-covariance — no second pass over the rows.
    evals, Q = eigh(stats.G_total + eye)
    factors = RidgeFactors(basis=Q, evals=evals, primal=True)
    W = solve(factors, stats.C_total, lams[best])
    return RidgeCVResult(weights=W, best_lambda=lams[best], best_index=best,
                         cv_scores=cv_scores)


def _ridge_cv_dual(X: jax.Array, Y: jax.Array,
                   cfg: RidgeCVConfig) -> RidgeCVResult:
    """Dual CV on per-fold kernel blocks of one ``XXᵀ``.

    ``K = XXᵀ`` is accumulated once; every split's training kernel is the
    static block ``K[tr, tr]`` and the validation predictions are
    ``K[val, tr] · α(λ)`` — algebraically identical to ``X_val W(λ)`` but
    without rebuilding any kernel or materialising per-λ weights.
    """
    n, p = X.shape
    bounds = foldstats.fold_bounds(n, cfg.n_folds)
    K = xxt(X, use_pallas=cfg.use_pallas)             # one n×n accumulation
    lams = _lambda_grid(cfg)
    per_lambda_scores = []
    for lo, hi in bounds:
        tr = np.concatenate([np.arange(lo), np.arange(hi, n)])
        K_tr = K[tr][:, tr]                           # static block slice
        evals, P_ = eigh(
            K_tr + cfg.jitter * jnp.eye(tr.size, dtype=jnp.float32))
        z = jnp.matmul(P_.T, Y[tr], preferred_element_type=jnp.float32)
        Bv = jnp.matmul(K[lo:hi][:, tr], P_,
                        preferred_element_type=jnp.float32)
        per_lambda_scores.append(
            _fold_scores(Bv, z, Y[lo:hi], evals, lams, cfg.scoring))
    cv_scores = jnp.mean(jnp.stack(per_lambda_scores), axis=0)    # (r,)
    best = jnp.argmax(cv_scores)
    evals, P_ = eigh(K + cfg.jitter * jnp.eye(n, dtype=jnp.float32))
    factors = RidgeFactors(basis=P_, evals=evals, primal=False)
    W = solve(factors, Y, lams[best], X=X, use_pallas=cfg.use_pallas)
    return RidgeCVResult(weights=W, best_lambda=lams[best], best_index=best,
                         cv_scores=cv_scores)


@partial(jax.jit, static_argnames=("cfg",))
def ridge_cv(X: jax.Array, Y: jax.Array, cfg: RidgeCVConfig = RidgeCVConfig()
             ) -> RidgeCVResult:
    """Cross-validated multi-target ridge — scikit-learn ``RidgeCV`` analog.

    Faithful to paper Algorithm 1 at batch granularity: every CV split gets
    its own factorisation of the training statistics (the ``svd(X_train)``
    line), the λ grid is swept diagonally, scores averaged over splits, a
    single λ selected for *all* targets (§2.2.4), and the final weights refit
    on the full training set.  Unlike the reference implementation the
    expensive row statistics are accumulated exactly once (see the module
    docstring's Algorithm-1 fidelity note: the downdate is exact algebra,
    not an approximation).
    """
    n, p = X.shape
    if cfg.resolve_method(n, p) == "eigh":
        return _ridge_cv_primal(X, Y, cfg)
    return _ridge_cv_dual(X, Y, cfg)


def ridge_cv_from_stats(stats: "foldstats.FoldStats",
                        cfg: RidgeCVConfig = RidgeCVConfig()
                        ) -> RidgeCVResult:
    """Fit the CV'd ridge from pre-accumulated fold statistics alone.

    The out-of-core entry point: ``stats`` may come from
    ``foldstats.compute_chunked`` over row batches that never coexist in
    device memory.  Validation scores are computed from sufficient
    statistics (``foldstats.validation_scores_from_stats``), so no
    validation rows are needed — primal/eigh only, since the dual kernel is
    an n×n object that defeats the point of streaming rows.
    """
    if cfg.method == "dual":
        raise ValueError("ridge_cv_from_stats is primal-only: the dual "
                         "kernel XXᵀ cannot be built from streamed row "
                         "statistics")
    from repro.kernels import ops
    return _cv_from_stats(stats, cfg, per_lambda=ops.on_tpu())


def _scoped(scope: str, fn, **jit_kwargs):
    """``fn`` as one compiled program whose ops carry ``scope`` in their
    HLO ``op_name``, which a TPU trace shows as each op's ``tf_op``."""
    def program(*args):
        with jax.named_scope(scope):
            return fn(*args)
    program.__name__ = program.__qualname__ = scope
    return jax.jit(program, **jit_kwargs)


def _mean_scores(stats, f, Ws, scoring):
    return jnp.mean(foldstats.validation_scores_from_weights(
        stats, f, Ws, scoring), axis=1)


# The TPU path's programs.  The per-λ solve and the refit are the same
# blocked Cholesky solve, compiled once under each name.
_cv_solve = _scoped("cv_solve", spd.ridge_solve)
_refit = _scoped("refit", spd.ridge_solve)
_cv_score = _scoped("cv_score", _mean_scores, static_argnums=(3,))


def _cv_from_stats(stats: "foldstats.FoldStats", cfg: RidgeCVConfig, *,
                   per_lambda: bool) -> RidgeCVResult:
    """``ridge_cv_from_stats`` with its factorisation chosen.

    ``per_lambda=False`` (off the TPU): one ``eigh`` per fold serves the
    whole λ grid (Eq. 5).  ``per_lambda=True`` (the TPU's): a blocked
    Cholesky solve of ``G_tr + λI`` per λ (``core.spd``) — r
    factorisations per fold instead of one eigh, but the TPU compiles them
    at p = 16,384 in seconds, where its eigh does not compile in useful
    time (see ``core.spd``).  Off the TPU the eigh path stays: the
    whole-brain solver (``wholebrain.solver``) hoists the same per-fold
    eigh across its target blocks and its global λ mode is held bitwise to
    this function, which the per-λ path breaks (three more
    ``test_target_block_invariance_fixed`` cases fail); and on CPU the
    per-λ path roughly doubled the tier-1 suite's wall time (234 s →
    443 s, six workers).
    """
    from repro import obs

    p = stats.G.shape[1]
    traced = obs.current() is not None
    per_lambda_scores = []
    # Tracing note: every span here forces its outputs only when a tracer
    # is installed, so the recorded durations are compute, not async
    # dispatch — with tracing off nothing is synchronised here.  The
    # outer span keeps the name ``fit.eigh`` on both paths (a benchmark
    # metric reads it); ``method`` says which factorisation ran.
    # eye/λ-grid construction lives inside the span: their first-touch
    # dispatch cost belongs to the factorisation phase it feeds.  On the
    # per-λ path the span also says which band plan ran, from the shape.
    attrs = ({"method": "per_lambda_cholesky",
              "bands": len(spd.band_plan(p)),
              "factor_update_flops": spd.update_flops(p)}
             if per_lambda else {"method": "eigh"})
    with obs.span("fit.eigh", folds=stats.n_folds, p=p, **attrs):
        eye = cfg.jitter * jnp.eye(p, dtype=jnp.float32)
        lams = _lambda_grid(cfg)
        for f in range(stats.n_folds):
            with obs.span("fit.cv.solve", fold=f, lambdas=len(lams)):
                G_tr, C_tr = stats.train(f)
                if per_lambda:
                    solved = jnp.stack([_cv_solve(G_tr + eye, C_tr, lam)
                                        for lam in lams])
                else:
                    solved = eigh(G_tr + eye)
                if traced:
                    jax.block_until_ready(solved)
            with obs.span("fit.cv.score", fold=f):
                if per_lambda:
                    scores = _cv_score(stats, f, solved, cfg.scoring)
                else:
                    evals, Q = solved
                    scores = foldstats.validation_scores_from_stats(
                        stats, f, Q, evals, C_tr, lams, cfg.scoring)
                if traced:
                    jax.block_until_ready(scores)
            per_lambda_scores.append(scores)
        cv_scores = jnp.mean(jnp.stack(per_lambda_scores), axis=0)
        best = jnp.argmax(cv_scores)
        if traced:
            jax.block_until_ready(cv_scores)
    with obs.span("fit.solve", p=p):
        if per_lambda:
            W = _refit(stats.G_total + eye, stats.C_total, lams[best])
        else:
            evals, Q = eigh(stats.G_total + eye)
            factors = RidgeFactors(basis=Q, evals=evals, primal=True)
            W = solve(factors, stats.C_total, lams[best])
        if traced:
            jax.block_until_ready(W)
    return RidgeCVResult(weights=W, best_lambda=lams[best], best_index=best,
                         cv_scores=cv_scores)


@partial(jax.jit, static_argnames=("cfg",))
def ridge_cv_reference(X: jax.Array, Y: jax.Array,
                       cfg: RidgeCVConfig = RidgeCVConfig()) -> RidgeCVResult:
    """Seed implementation: per-fold re-accumulation (baseline, kept on
    purpose).

    For every split this concatenates the training rows and recomputes their
    Gram/kernel from scratch — ``(k−1)·np²`` of redundant ``T_W`` work that
    ``ridge_cv`` now derives by downdating.  Parity tests
    (``tests/test_foldstats.py``) and ``benchmarks/foldstats_bench.py``
    measure the new path against this one; do not use it elsewhere.
    """
    n, p = X.shape
    bounds = foldstats.fold_bounds(n, cfg.n_folds)
    per_lambda_scores = []
    for (lo, hi) in bounds:
        X_val, Y_val = X[lo:hi], Y[lo:hi]
        X_tr = jnp.concatenate([X[:lo], X[hi:]], axis=0)
        Y_tr = jnp.concatenate([Y[:lo], Y[hi:]], axis=0)
        factors = factorize(X_tr, cfg)
        rhs = gram_xty(X_tr, Y_tr) if factors.primal else Y_tr
        Ws = solve_lambda_grid(factors, rhs, cfg.lambdas,
                               X=None if factors.primal else X_tr,
                               use_pallas=cfg.use_pallas)
        preds = jnp.einsum("np,rpt->rnt", X_val, Ws,
                           preferred_element_type=jnp.float32)
        scores = jax.vmap(lambda Yp: _score(Y_val, Yp, cfg.scoring))(preds)
        per_lambda_scores.append(scores)
    cv_scores = jnp.mean(jnp.stack(per_lambda_scores), axis=0)    # (r,)
    best = jnp.argmax(cv_scores)
    lams = _lambda_grid(cfg)
    # Refit on the full data with the selected λ.
    factors = factorize(X, cfg)
    rhs = gram_xty(X, Y) if factors.primal else Y
    W = solve(factors, rhs, lams[best], X=None if factors.primal else X)
    return RidgeCVResult(weights=W, best_lambda=lams[best], best_index=best,
                         cv_scores=cv_scores)


def predict(X: jax.Array, W: jax.Array) -> jax.Array:
    return jnp.matmul(X, W, preferred_element_type=jnp.float32)
