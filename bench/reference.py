"""Plain references: what the system under test must compute, written
straightforwardly and importing nothing of the program.

* ``ridge_cv_curve`` / ``ridge_refit`` / ``normal_residual`` — k-fold
  cross-validated ridge over a λ grid: each fold's Gram from its own
  rows, a training Gram as the sum of the other folds', every λ solved by
  conjugate gradients, held-out R² from explicit predictions, the refit
  at a given λ, and how well a given W solves the normal equations.  Runs
  on the device in f32 at a stated matmul precision, after the program's
  state is freed.
* ``predictions`` / ``pearson`` — a served prediction and its Pearson r,
  in float64 on the host.

The control of a cell is the reference computed one precision step below
the configuration's (``high``, three bf16 passes, for f32 at ``highest``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum`` at a stated matmul precision, f32 accumulation.

    On the TPU ``high`` (three bf16 passes) and ``default`` (one) are
    XLA's own.  Other backends ignore the precision of an f32 product, so
    there the two are spelled out as products of bf16 halves accumulated
    in f32, which is what the TPU's matrix unit does.  (On the TPU the
    spelled-out form is not used: XLA may fold its f32→bf16→f32 round
    trip away, and it then reads as one pass.)"""
    if precision == "highest" or jax.default_backend() == "tpu":
        return jnp.einsum(spec, a, b, precision=precision,
                          preferred_element_type=jnp.float32)

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    if precision == "default":
        return dot(a_hi, b_hi)
    if precision == "high":
        return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))
    raise ValueError(f"unknown precision {precision!r}")


class ReferenceError(RuntimeError):
    """The reference itself did not reach its accuracy (no comparison)."""


def fold_bounds(n: int, k: int) -> list[tuple[int, int]]:
    """Contiguous folds, the first n % k one row longer (scikit-learn
    ``KFold`` without shuffling)."""
    sizes = [n // k + (i < n % k) for i in range(k)]
    edges = np.concatenate([[0], np.cumsum(sizes)])
    return [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]


@partial(jax.jit, static_argnames=("precision",))
def _gram(Xa, Ya, precision):
    return (einsum("np,nq->pq", Xa, Xa, precision),
            einsum("np,nt->pt", Xa, Ya, precision))


def fold_stats(X, Y, k: int, precision: str) -> list[tuple]:
    """(XᵀX, XᵀY) of each fold's own rows."""
    return [_gram(X[lo:hi], Y[lo:hi], precision)
            for lo, hi in fold_bounds(X.shape[0], k)]


def summed(stats, skip: int | None = None):
    """(G, C) summed over the folds, leaving out fold ``skip``."""
    parts = [s for f, s in enumerate(stats) if f != skip]
    G, C = parts[0]
    for g, c in parts[1:]:
        G, C = G + g, C + c
    return G, C


@partial(jax.jit, static_argnames=("precision", "iters"))
def cg_solve(A, B, lams, *, precision: str, iters: int = 200,
             tol: float = 1e-7):
    """``(A + λ_i I) W_i = B`` for every λ_i: conjugate gradients on each
    column, all columns and λs at once.  Stops when every column's
    recurrence residual is under ``tol`` relative to its right-hand side,
    or after ``iters`` steps.  Returns ``W`` (r, p, t), the true
    residual ``max ‖(A + λI)w − b‖ / ‖b‖`` and the steps taken."""
    r_lams = lams[:, None, None]

    def op(V):
        return einsum("pq,rqt->rpt", A, V, precision) + r_lams * V

    Bb = jnp.broadcast_to(B, (lams.shape[0],) + B.shape)
    bb = jnp.sum(Bb * Bb, axis=1)
    W0 = jnp.zeros_like(Bb)

    def cond(state):
        i, _, _, _, rr = state
        return (i < iters) & jnp.any(rr > tol * tol * bb)

    def body(state):
        i, W, R, P, rr = state
        AP = op(P)
        pap = jnp.sum(P * AP, axis=1)
        alpha = jnp.where(pap > 0, rr / jnp.where(pap > 0, pap, 1.0), 0.0)
        W = W + alpha[:, None] * P
        R = R - alpha[:, None] * AP
        rr_new = jnp.sum(R * R, axis=1)
        beta = jnp.where(rr > 0, rr_new / jnp.where(rr > 0, rr, 1.0), 0.0)
        return i + 1, W, R, R + beta[:, None] * P, rr_new

    it, W, _, _, _ = jax.lax.while_loop(cond, body, (0, W0, Bb, Bb, bb))
    res = jnp.sqrt(jnp.sum((op(W) - Bb) ** 2, axis=1) / bb)
    return W, jnp.max(res), it


@partial(jax.jit, static_argnames=("precision",))
def _r2(Xv, Yv, Ws, precision):
    """Mean over targets of held-out R² for each W in Ws (r, p, t)."""
    P = einsum("np,rpt->rnt", Xv, Ws, precision)
    ss_res = jnp.sum((Yv[None] - P) ** 2, axis=1)
    ss_tot = jnp.sum((Yv - Yv.mean(0)) ** 2, axis=0)
    return jnp.mean(1.0 - ss_res / ss_tot, axis=1)


#: The reference solve must reach this true relative residual, or it
#: gives no number at all.
SOLVE_RESIDUAL = 1e-4


def _solve(A, B, lams, precision, check=True):
    W, res, _ = cg_solve(A, B, jnp.asarray(lams, jnp.float32),
                         precision=precision)
    res = float(res)
    if check and not res <= SOLVE_RESIDUAL:
        raise ReferenceError(f"reference CG residual {res:.3e} > "
                             f"{SOLVE_RESIDUAL}")
    return W


def ridge_cv_curve(X, Y, stats, lambdas, jitter: float, precision: str,
                   check: bool = True) -> np.ndarray:
    """Mean held-out R² per λ, averaged over the contiguous folds whose
    ``fold_stats`` are ``stats``.  ``check=False`` (the control) takes
    the solves as they come, converged or not."""
    lams = np.asarray(lambdas, np.float64) + jitter
    scores = []
    for f, (lo, hi) in enumerate(fold_bounds(X.shape[0], len(stats))):
        G, C = summed(stats, skip=f)
        W = _solve(G, C, lams, precision, check)
        del G, C
        scores.append(np.asarray(_r2(X[lo:hi], Y[lo:hi], W, precision),
                                 np.float64))
        del W
    return np.mean(scores, axis=0)


def ridge_refit(G, C, lam: float, jitter: float, precision: str,
                check: bool = True):
    """W at λ from all rows' (G, C), on the device."""
    return _solve(G, C, [lam + jitter], precision, check)[0]


@jax.jit
def _residual(G, C, lam, W):
    R = jnp.matmul(G, W, precision="highest") + lam * W - C
    return jnp.max(jnp.linalg.norm(R, axis=0) / jnp.linalg.norm(C, axis=0))


def normal_residual(G, C, lam: float, W) -> float:
    """max over targets of ‖(G + λI)w − c‖ / ‖c‖: how well ``W`` solves
    the reference's normal equations, in full f32."""
    return float(_residual(G, C, jnp.float32(lam), jnp.asarray(W)))


# -- serving ----------------------------------------------------------------

def predictions(X: np.ndarray, W: np.ndarray, mu_x, sd_x, mu_y,
                sd_y) -> np.ndarray:
    """Raw-unit predictions ``((X − μx)/σx) W · σy + μy`` in float64."""
    f = np.float64
    Xs = (X.astype(f) - np.asarray(mu_x, f)) / np.asarray(sd_x, f)
    return (Xs @ np.asarray(W, f)) * np.asarray(sd_y, f) + np.asarray(mu_y, f)


def pearson(Y: np.ndarray, P: np.ndarray) -> np.ndarray:
    """Per-column Pearson r of targets and predictions, float64."""
    Y = np.asarray(Y, np.float64)
    P = np.asarray(P, np.float64)
    Yc, Pc = Y - Y.mean(0), P - P.mean(0)
    return (Yc * Pc).sum(0) / np.sqrt((Yc ** 2).sum(0) * (Pc ** 2).sum(0))


@partial(jax.jit, static_argnames=("precision",))
def predictions_device(X, W, mu_x, sd_x, mu_y, sd_y, precision: str):
    """The same prediction on the device in f32 at ``precision`` — the
    serving cells' control, one step below the configuration's."""
    Xs = (X - mu_x) / sd_x
    return einsum("np,pt->nt", Xs, W, precision) * sd_y + mu_y
