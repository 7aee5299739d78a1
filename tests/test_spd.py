"""The blocked Cholesky ridge solve (``core.spd``) and the per-λ CV path
that the TPU runs, checked on CPU against float64 numpy and against the
one-eigh-per-fold path."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import foldstats, ridge, spd
from repro.core.ridge import RidgeCVConfig
from repro.data import fmri


# Band plans of ``spd.cholesky`` (``spd.BANDS`` = 8): one step; three
# steps of an identity-padded p; fewer steps than bands; a step count not
# divisible by the bands, padded or not; a multiple of the bands.
@pytest.mark.parametrize("p,block", [(64, 16), (100, 32), (300, 128),
                                     (32, 32), (200, 16), (192, 16),
                                     (256, 16)])
def test_ridge_solve_matches_float64(p, block):
    """Factor and solve against float64, including p not a multiple of
    the block (identity-padded tail)."""
    rng = np.random.default_rng(p)
    X = rng.standard_normal((2 * p, p))
    G, C = X.T @ X, rng.standard_normal((p, 7))
    A = spd._padded(jnp.asarray(G + 3.0 * np.eye(p), jnp.float32), block)
    L = np.asarray(spd.cholesky(A, block=block), np.float64)
    L_ref = np.linalg.cholesky(np.asarray(A, np.float64))
    assert np.all(np.triu(L, 1) == 0)
    assert np.linalg.norm(L - L_ref) / np.linalg.norm(L_ref) < 1e-5
    W = spd.ridge_solve(jnp.asarray(G, jnp.float32),
                        jnp.asarray(C, jnp.float32), jnp.float32(3.0),
                        block=block)
    ref = np.linalg.solve(G + 3.0 * np.eye(p), C)
    assert np.linalg.norm(np.asarray(W) - ref) / np.linalg.norm(ref) < 1e-5


def test_band_plan_at_paper_width():
    """At p = 16,384 and the 512 block the bands cover every step once,
    each band's static rectangle holds every step's live rows and
    finished columns, and the updates cost under a quarter of 2p³."""
    p, block = 16_384, spd.BLOCK
    plan = spd.band_plan(p, block)
    assert len(plan) == spd.BANDS
    steps = [k for band in plan for k in range(band.first, band.stop)]
    assert steps == list(range(p // block))
    for band in plan:
        for k in range(band.first, band.stop):
            assert band.row0 <= k * block <= band.col_end
    assert spd.update_flops(p, block) <= 0.25 * 2 * p ** 3


def test_cholesky_factor_is_lower_and_exact():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((200, 96))
    A = X.T @ X + np.eye(96)
    L = np.asarray(spd.cholesky(jnp.asarray(A, jnp.float32), block=32),
                   np.float64)
    assert np.all(np.triu(L, 1) == 0)
    assert np.abs(L @ L.T - A).max() / np.abs(A).max() < 1e-5


@pytest.fixture(scope="module")
def subject_stats():
    X, Y, _ = fmri.generate(jax.random.PRNGKey(0),
                            fmri.SubjectSpec(n=1500, p=96, t=40))
    return foldstats.compute(X, Y, 5)


@pytest.mark.parametrize("scoring", ["r2", "r"])
def test_per_lambda_cv_matches_eigh_path(subject_stats, scoring):
    """The TPU's factorisation selects the same λ and the same weights
    (to f32 rounding) as one eigh per fold."""
    cfg = RidgeCVConfig(scoring=scoring)
    a = ridge._cv_from_stats(subject_stats, cfg, per_lambda=False)
    b = ridge._cv_from_stats(subject_stats, cfg, per_lambda=True)
    assert float(a.best_lambda) == float(b.best_lambda)
    np.testing.assert_allclose(np.asarray(b.cv_scores),
                               np.asarray(a.cv_scores), atol=1e-6)
    wa, wb = np.asarray(a.weights), np.asarray(b.weights)
    assert np.linalg.norm(wb - wa) / np.linalg.norm(wa) < 1e-5


def test_scores_from_weights_match_eigenbasis(subject_stats):
    """Per-target fold scores from explicit weights equal the eigenbasis
    form of the same weights."""
    st = subject_stats
    lams = jnp.asarray(ridge.PAPER_LAMBDA_GRID, jnp.float32)
    G_tr, C_tr = st.train(2)
    evals, Q = ridge.eigh(G_tr)
    Ws = jnp.stack([Q @ ((Q.T @ C_tr) / (evals + lam)[:, None])
                    for lam in lams])
    for scoring in ("r2", "r"):
        direct = foldstats.validation_scores_from_weights(st, 2, Ws, scoring)
        eig = foldstats.validation_scores_per_target(st, 2, Q, evals, C_tr,
                                                     lams, scoring)
        np.testing.assert_allclose(np.asarray(direct), np.asarray(eig),
                                   atol=2e-5)
