"""Blocked Cholesky ridge solves whose compiled size does not grow with p.

``(G + λI) W = C`` for a symmetric positive definite ``G`` (p × p), as a
left-looking blocked Cholesky and two blocked triangular sweeps, each a
``lax.fori_loop`` over ``p / block`` column blocks.  The loop body is one
fixed set of programs (a banded update, a ``block × block`` Cholesky and
triangular solve), so the compiled code is the same size at any p.

This is the TPU's factorisation for the streamed CV.  There, XLA expands
``cholesky``/``eigh`` into code that grows with p: compiled for v5e at
p = 16,384 the plain Cholesky solve is 417 MB of code and 212 s to
compile, the QDWH eigh does not finish compiling, and the Jacobi eigh
custom call takes 2.7 s at p = 2,048 and grows as p³.

The update of step k needs only the rows at or below ``lo = k·block`` and
the finished columns before ``lo``.  The steps are split into
``min(BANDS, p / block)`` consecutive bands (``band_plan``); each band
reads one static rectangle of the factor, from its first step's ``lo``
down and up to its last step's ``lo`` across, so each band is one fixed
shape.  A factorisation costs ``update_flops(p)`` for its updates: at
p = 16,384 and a 512 block, 1.91e12 FLOPs, 1.3 times a dense one's p³/3
(a full-height update at every step would cost 2p³, 8.80e12).

Every matmul runs at the fit's precision, ``kernels.gram.PRECISION``.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.gram import PRECISION

BLOCK = 512
BANDS = 8          # static bands of the Cholesky update (``band_plan``)


class Band(NamedTuple):
    """Steps ``first`` to ``stop − 1`` of the left-looking Cholesky, whose
    update reads the factor's rows from ``row0`` down and its columns
    before ``col_end``."""
    first: int
    stop: int
    row0: int      # the first step's lo
    col_end: int   # the last step's lo


def band_plan(p: int, block: int = BLOCK) -> tuple[Band, ...]:
    """The bands of a factorisation of a p × p matrix (p padded up to a
    multiple of ``block``): ``min(BANDS, nb)`` runs of consecutive steps,
    whose lengths differ by at most one."""
    nb = -(-p // block)
    s = min(BANDS, nb)
    cuts = [nb * i // s for i in range(s + 1)]
    return tuple(Band(a, b, a * block, (b - 1) * block)
                 for a, b in zip(cuts, cuts[1:]))


def update_flops(p: int, block: int = BLOCK) -> int:
    """FLOPs of the updates of one factorisation, as ``cholesky`` runs
    them: each step multiplies its band's (q − row0, col_end) rectangle
    by a (col_end, block) row block, q being p padded."""
    q = -(-p // block) * block
    return sum((b.stop - b.first) * 2 * (q - b.row0) * b.col_end * block
               for b in band_plan(p, block))


def _dot(a, b):
    return jnp.matmul(a, b, precision=PRECISION,
                      preferred_element_type=jnp.float32)


def _padded(A: jax.Array, block: int) -> jax.Array:
    """A padded to a multiple of ``block`` with an identity tail (the
    padded system decouples, and its factor is the identity there)."""
    p = A.shape[0]
    q = -(-p // block) * block
    if q == p:
        return A
    A = jnp.pad(A, ((0, q - p), (0, q - p)))
    tail = jnp.arange(q) >= p
    return A + jnp.diag(tail.astype(A.dtype))


@partial(jax.jit, static_argnames=("block",))
def cholesky(A: jax.Array, block: int = BLOCK) -> jax.Array:
    """Lower Cholesky factor of SPD ``A`` (p × p, p a multiple of
    ``block``), left-looking by column blocks.

    Step k: column block k minus the contribution of the finished blocks
    (``L[lo:, :lo] @ L[lo:lo+b, :lo]ᵀ``, read through its band's static
    rectangle, with the row block's unfinished columns masked to zero),
    its diagonal block factored, the rows below solved against that
    factor, the rows above zeroed.  Finished columns are kept in place,
    so the buffer ends as L.
    """
    p = A.shape[0]
    assert A.shape == (p, p) and p % block == 0, (A.shape, block)
    plan = band_plan(p, block)
    band_of = jnp.asarray(np.repeat(np.arange(len(plan)),
                                    [b.stop - b.first for b in plan]),
                          jnp.int32)
    rows = jnp.arange(p)[:, None]

    def banded(band):
        r, c = band.row0, band.col_end

        def update(L, lo):                  # column block lo, rows r:
            col = jax.lax.dynamic_slice(L, (r, lo), (p - r, block))
            if c:
                row_blk = jax.lax.dynamic_slice(L, (lo, 0), (block, c))
                row_blk = jnp.where(jnp.arange(c)[None, :] < lo, row_blk,
                                    0.0)
                col = col - _dot(L[r:, :c], row_blk.T)
            return jnp.pad(col, ((r, 0), (0, 0)))   # rows above r: unused
        return update

    updates = [banded(band) for band in plan]

    def step(k, L):
        lo = k * block
        col = jax.lax.switch(band_of[k], updates, L, lo)        # (p, b)
        diag = jax.lax.dynamic_slice(col, (lo, 0), (block, block))
        Lkk = jnp.linalg.cholesky(diag)
        below = jax.lax.linalg.triangular_solve(
            Lkk, col, left_side=False, lower=True, transpose_a=True)
        r = rows[:, :1]
        new = jnp.where(r < lo, 0.0,
                        jnp.where(r < lo + block,
                                  jnp.tile(Lkk, (p // block, 1)), below))
        return jax.lax.dynamic_update_slice(L, new, (0, lo))

    with jax.default_matmul_precision(PRECISION):
        return jax.lax.fori_loop(0, p // block, step, A)


@partial(jax.jit, static_argnames=("block",))
def cho_solve(L: jax.Array, B: jax.Array, block: int = BLOCK) -> jax.Array:
    """``X`` with ``L Lᵀ X = B``, by a forward then a backward sweep over
    column blocks of ``L`` (p a multiple of ``block``)."""
    p = L.shape[0]
    nb = p // block
    rows = jnp.arange(p)[:, None]

    def fwd(k, Y):                                   # L Y = B
        lo = k * block
        Lk = jax.lax.dynamic_slice(L, (lo, 0), (block, p))
        Lk = jnp.where(jnp.arange(p)[None, :] < lo, Lk, 0.0)
        Lkk = jax.lax.dynamic_slice(L, (lo, lo), (block, block))
        rhs = jax.lax.dynamic_slice(B, (lo, 0), (block, B.shape[1]))
        yk = jax.lax.linalg.triangular_solve(
            Lkk, rhs - _dot(Lk, Y), left_side=True, lower=True)
        return jax.lax.dynamic_update_slice(Y, yk, (lo, 0))

    def bwd(j, X):                                   # Lᵀ X = Y
        lo = (nb - 1 - j) * block
        Lk = jax.lax.dynamic_slice(L, (0, lo), (p, block))     # L[:, blk]
        Lk = jnp.where(rows >= lo + block, Lk, 0.0)            # rows below
        Lkk = jax.lax.dynamic_slice(L, (lo, lo), (block, block))
        rhs = jax.lax.dynamic_slice(X, (lo, 0), (block, X.shape[1]))
        xk = jax.lax.linalg.triangular_solve(
            Lkk, rhs - _dot(Lk.T, X), left_side=True, lower=True,
            transpose_a=True)
        return jax.lax.dynamic_update_slice(X, xk, (lo, 0))

    with jax.default_matmul_precision(PRECISION):
        Y = jax.lax.fori_loop(0, nb, fwd, jnp.zeros_like(B))
        return jax.lax.fori_loop(0, nb, bwd, Y)


@partial(jax.jit, static_argnames=("block",))
def ridge_solve(G: jax.Array, C: jax.Array, lam: jax.Array,
                block: int = BLOCK) -> jax.Array:
    """``W = (G + λI)⁻¹ C`` for SPD ``G`` (p × p) and ``C`` (p × t)."""
    p, t = C.shape
    A = _padded(G + lam * jnp.eye(p, dtype=G.dtype), block)
    B = jnp.pad(C, ((0, A.shape[0] - p), (0, 0)))
    return cho_solve(cholesky(A, block), B, block)[:p]
