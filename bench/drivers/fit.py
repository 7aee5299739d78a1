"""Fits of one subject's store, back to back.

Set-up makes the subject from the seed on the device, writes it through
the program's ``RunStore`` writer and runs one whole fit, which compiles
every program the window uses.  The window then runs whole fits — store
open, ``BrainEncoder.fit(store=)``, ``save`` — until ``--seconds`` have
passed; the last one finishes.  Afterwards every committed bundle, read
back through the program's bundle reader, is compared with the plain
reference: the λ chosen, the weights at that λ, and how well they solve
the reference's normal equations.  (The CV curve itself is logged, not
compared: the control moves it no more than sound runs do, see PERF.md.)
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import gen, reference
from bench.harness import Check, Harness, log

KINDS = ("fit_back_to_back",)


def _encoder_config(config: dict, budget: int):
    from repro.encoding import EncoderConfig

    prog = config["program"]
    return EncoderConfig(n_folds=config["k"],
                         lambdas=tuple(config["lambdas"]),
                         jitter=prog["jitter"],
                         chunk_rows=prog["chunk_rows"],
                         device_memory_budget=budget)


def fit_once(store_dir: str, bundle_dir: str, cfg) -> dict:
    """One subject from store open to committed bundle."""
    from repro.data.store import RunStore
    from repro.encoding import BrainEncoder

    t0 = time.perf_counter()
    store = RunStore.open(store_dir)
    enc = BrainEncoder(cfg).fit(store=store)
    enc.save(bundle_dir, overwrite=True)
    t1 = time.perf_counter()
    d = enc.report_.decision
    return {"t0": t0, "dur_s": t1 - t0, "bundle": bundle_dir,
            "method": d.method, "use_pallas": bool(d.use_pallas),
            "read_stall_s": float(enc.stream_stats_["read_stall_s"]),
            "chunks": int(enc.stream_stats_["chunks"]),
            "chunk_rows": cfg.chunk_rows}


def read_bundle(path: str) -> dict:
    """λ, CV curve and W of a committed bundle, as the program reads it."""
    from repro.serving_encoders import EncoderBundle

    b = EncoderBundle.open(path)
    arrays = b.load_arrays([k for k in b._leaves() if not k.startswith("W/")])
    W = np.concatenate([b.load_weight_shard(i)
                        for i in range(len(b.weight_shard_bounds()))], 1)
    return {"lambda": float(np.asarray(arrays["best_lambda"]).ravel()[0]),
            "curve": np.asarray(arrays["cv_scores"], np.float64).ravel(),
            "W": W}


def compare(config: dict, limits: dict, seed: int, bundles: list[str],
            precision: str, program=None) -> list[Check]:
    """The checks of a fit cell: every committed bundle against the plain
    reference at ``precision``.  ``program`` stands in for the bundles
    (the control): a function of (X, Y) that returns what a bundle
    holds."""
    t0 = time.perf_counter()
    X, Y = gen.subject_for(seed, config)
    lams = config["lambdas"]
    jitter = config["program"]["jitter"]
    answers = ([program(X, Y)] if program is not None
               else [read_bundle(b) for b in bundles])
    stats = reference.fold_stats(X, Y, config["k"], precision)
    curve = reference.ridge_cv_curve(X, Y, stats, lams, jitter, precision)
    G, C = reference.summed(stats)
    del stats, X, Y
    refits = {}
    mismatch = w_err = resid = 0.0
    for a in answers:
        lam = a["lambda"]
        mismatch = max(mismatch, float(lam != lams[int(np.argmax(curve))]))
        if lam not in refits:
            refits[lam] = np.asarray(reference.ridge_refit(
                G, C, lam, jitter, precision), np.float64)
        W_ref = refits[lam]
        w_err = max(w_err, float(np.linalg.norm(a["W"] - W_ref)
                                 / np.linalg.norm(W_ref)))
        resid = max(resid, reference.normal_residual(G, C, lam + jitter,
                                                     a["W"]))
    log(f"bench: reference check {time.perf_counter() - t0:.1f} s; "
        f"curve {np.round(curve, 7).tolist()}; curve gap "
        f"{max(float(np.max(np.abs(a['curve'] - curve))) for a in answers)!r}")
    return [Check("lambda_mismatch", mismatch, limits["lambda_mismatch"]),
            Check("weights_rel_err", w_err, limits["weights_rel_err"]),
            Check("normal_eq_residual", resid, limits["normal_eq_residual"])]


def control_answer(config: dict, precision: str):
    """The reference in the program's place, at ``precision``."""
    def program(X, Y):
        lams = config["lambdas"]
        jitter = config["program"]["jitter"]
        stats = reference.fold_stats(X, Y, config["k"], precision)
        curve = reference.ridge_cv_curve(X, Y, stats, lams, jitter,
                                         precision, check=False)
        G, C = reference.summed(stats)
        del stats
        lam = lams[int(np.argmax(curve))]
        W = reference.ridge_refit(G, C, lam, jitter, precision, check=False)
        return {"lambda": lam, "curve": curve, "W": np.asarray(W)}
    return program


def control_checks(cell, seed: int, seconds: float,
                   precision: str) -> list[Check]:
    """The checks with the reference at ``precision`` in the program's
    place (``seconds`` plays no part: a fit's answer does not depend on
    the window)."""
    cfg = cell.config
    return compare(cfg, cell.limits, seed, [], cfg["precision"],
                   program=control_answer(cfg, precision))


def tiny(cell):
    """The cell at a size the CPU fits in a test, streamed in four
    chunks."""
    cfg = dict(cell.config, n=1536, p=128, t=32)
    cfg["program"] = dict(cfg["program"], chunk_rows=384)
    return dataclasses.replace(cell, config=cfg)


def faults(kind: str) -> dict:
    """Planted faults: the chunk update returns its state unchanged, or
    leaves out half of its rows; the solve's answer is altered."""
    import jax.numpy as jnp
    from repro.core import foldstats, ridge

    update = foldstats._FixedShapeUpdate.__call__
    solve = ridge.ridge_cv_from_stats

    def unchanged(self, stats, *a, **k):
        return stats

    def half_batch(self, stats, X, Y, onehot, slot_fold, **k):
        onehot = jnp.asarray(onehot)
        onehot = onehot.at[onehot.shape[0] // 2:].set(0)
        return update(self, stats, X, Y, onehot, slot_fold, **k)

    def altered(stats, cfg):
        res = solve(stats, cfg)
        return dataclasses.replace(
            res, weights=res.weights.at[:, 0].multiply(1.01))

    return {"state_unchanged": (foldstats._FixedShapeUpdate, "__call__",
                                unchanged),
            "half_batch": (foldstats._FixedShapeUpdate, "__call__",
                           half_batch),
            "answer_altered": (ridge, "ridge_cv_from_stats", altered)}


def setup_store(h: Harness, seed: int, config: dict) -> str:
    from repro.data.store import RunStore

    X, Y = gen.subject_for(seed, config)
    X, Y = np.asarray(X), np.asarray(Y)
    store_dir = h.path("store")
    RunStore.create(store_dir, n_folds=config["k"]).write(X, Y, "run-000")
    return store_dir


def run(h: Harness, seed: int, seconds: float, budget: int) -> None:
    rec = h.rec
    config = rec.config
    cfg = _encoder_config(config, budget)
    t0 = time.perf_counter()
    store_dir = setup_store(h, seed, config)
    gc.collect()
    t1 = time.perf_counter()
    for i in range(rec.traffic["warmup_fits"]):
        fit_once(store_dir, h.path(f"warm-{i}"), cfg)
    log(f"bench: set-up: store {t1 - t0:.1f} s, warm-up fits "
        f"{time.perf_counter() - t1:.1f} s")

    with h.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            rec.fits.append(fit_once(
                store_dir, h.path(f"bundle-{len(rec.fits)}"), cfg))
    rec.attempted = len(rec.fits)
    for f in rec.fits:
        for name in ("fit.stats", "fit.eigh"):
            spans = h.span_events(f["t0"], f["t0"] + f["dur_s"], name)
            if spans:
                f[name] = sum(s["dur"] for s in spans)
    rec.memory_peak_bytes = h.memory_peak()
    gc.collect()
    rec.checks = compare(config, rec.limits, seed,
                         [f["bundle"] for f in rec.fits],
                         config["precision"])
    bad = [f for f in rec.fits if f["method"] != config["program"]["method"]]
    if bad:
        rec.checks.append(Check("fits_off_configured_path", len(bad), 0))
