"""Inputs and weights made from the seed, on the device, in one jitted call.

The subject generator is a copy of ``repro.data.fmri.generate`` (a
planted linear model with target-dependent SNR, slow drift and noise,
targets normalised per column), with its one matmul at full f32
precision.  The benchmark imports nothing of the program for its inputs,
so the reference can regenerate exactly what the program was given.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, *path: int):
    """A PRNG key for ``seed`` (any non-negative integer below 2**64) and
    a path of sub-streams.  Both 32-bit halves of the seed are used."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


@partial(jax.jit, static_argnames=(
    "n", "p", "t", "frac_responsive", "snr", "drift_amp", "tr_seconds"))
def subject(k, *, n: int, p: int, t: int, frac_responsive: float,
            snr: float, drift_amp: float, tr_seconds: float):
    """(X (n, p), Y (n, t)) of one synthetic subject."""
    kx, kw, kn, kd = jax.random.split(k, 4)
    X = jax.random.normal(kx, (n, p), jnp.float32)
    mask = jnp.arange(t) < int(t * frac_responsive)
    W = jax.random.normal(kw, (p, t), jnp.float32) / np.sqrt(p)
    W = W * mask[None, :].astype(jnp.float32)
    signal = jnp.matmul(X, W, precision="highest") * snr
    noise = jax.random.normal(kn, (n, t), jnp.float32)
    tt = jnp.arange(n, dtype=jnp.float32)[:, None] * tr_seconds
    phase = jax.random.uniform(kd, (1, t)) * 2 * jnp.pi
    Y = signal + noise + drift_amp * jnp.sin(2 * jnp.pi * 0.003 * tt + phase)
    Y = (Y - Y.mean(0, keepdims=True)) / (Y.std(0, keepdims=True) + 1e-6)
    return X, Y


def subject_for(seed: int, config: dict):
    """The subject of ``config`` (its ``data`` group) for ``seed``."""
    d = config["data"]
    return subject(key(seed, 0), n=config["n"], p=config["p"],
                   t=config["t"], frac_responsive=d["frac_responsive"],
                   snr=d["snr_responsive"], drift_amp=d["drift_amp"],
                   tr_seconds=d["tr_seconds"])


@partial(jax.jit, static_argnames=("p", "t"))
def encoder(k, *, p: int, t: int):
    """A served subject's encoder: W (p, t) and the standardiser it was
    fitted with (μ/σ of the features and of the targets)."""
    kw, k1, k2, k3, k4 = jax.random.split(k, 5)
    W = jax.random.normal(kw, (p, t), jnp.float32) / np.sqrt(p)
    mu_x = 0.5 * jax.random.normal(k1, (p,), jnp.float32)
    sd_x = 0.5 + jax.random.uniform(k2, (p,), jnp.float32)
    mu_y = 0.5 * jax.random.normal(k3, (t,), jnp.float32)
    sd_y = 0.5 + jax.random.uniform(k4, (t,), jnp.float32)
    return W, mu_x, sd_x, mu_y, sd_y


def encoder_for(seed: int, config: dict):
    return encoder(key(seed, 1), p=config["p"], t=config["t"])


@partial(jax.jit, static_argnames=("rows", "cols"))
def pool(k, *, rows: int, cols: int):
    """A block of raw rows that requests are cut from."""
    return jax.random.normal(k, (rows, cols), jnp.float32)


def feature_pool(seed: int, rows: int, config: dict) -> np.ndarray:
    """Raw stimulus features: standard normal rows, on the host."""
    return np.asarray(pool(key(seed, 2), rows=rows, cols=config["p"]))


def target_pool(seed: int, rows: int, config: dict) -> np.ndarray:
    """Measured targets for scored requests, on the host."""
    return np.asarray(pool(key(seed, 3), rows=rows, cols=config["t"]))
