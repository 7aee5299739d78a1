"""Fits of one subject from transcript tokens, back to back: the feature
backbone, the delay embedding and the parcel fit in one run.

Set-up makes the backbone's weights and the subject's transcripts from the
seed, computes the plain reference's features of every TR
(``bench/reference_backbone.py``), plants the targets on their delays as
the parcel cell plants its own, writes the targets and transcripts through
the program's writer (``repro.encoding.backbone.write_subject``) and runs
one whole fit, which compiles every program the window uses.  The window
runs whole fits — store open, ``BrainEncoder.fit_chunks`` over the
program's ``BackboneSource``, ``save`` — until ``--seconds`` have passed;
the last one finishes.

Afterwards the program's compiled extraction runs once more and its
features are compared with the reference's over all rows; then every layer
of the runs packed into the first two rows is compared on the program's
own input to it, with the program's router picks, and those picks with the
reference's own; then every committed bundle is compared, as the parcel
cell compares its own, with the reference ridge on the program's
re-extracted features, delayed by the reference.  The reference's features
are the check's and not the program's set-up: their seconds are taken out
of ``setup_s``.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import gen, reference, reference_backbone as rb
from bench.harness import Check, Harness, log

KINDS = ("backbone_fit_back_to_back",)


def model_config(config: dict):
    """The program's model configuration with the cell's numbers."""
    from repro import configs

    m = rb.dims(config)
    base = configs.get_config(config["catalog_name"])
    return dataclasses.replace(
        base, n_layers=len(m["layers"]), pattern=m["layers"], d_model=m["d"],
        n_heads=m["Hq"], n_kv_heads=m["Hk"], head_dim=m["D"], d_ff=m["fs"],
        vocab=m["V"], norm_eps=m["eps"], embedding_multiplier=m["emb"],
        residual_multiplier=m["res"], attention_multiplier=m["att"],
        moe=dataclasses.replace(base.moe, n_experts=m["E"],
                                top_k=m["top_k"],
                                held=tuple(range(m["held"])),
                                d_expert=m["f"]),
        ssm=dataclasses.replace(base.ssm, d_state=m["N"], head_dim=m["P"],
                                n_groups=m["G"], conv_kernel=m["K"],
                                chunk=m["Q"],
                                expand=config["mamba_expand"]))


def _encoder_config(config: dict, budget: int):
    from repro.encoding import EncoderConfig

    prog = config["program"]
    return EncoderConfig(n_folds=config["k"],
                         lambdas=tuple(config["lambdas"]),
                         jitter=prog["jitter"],
                         chunk_rows=prog["chunk_rows"],
                         device_memory_budget=budget)


def _run_lengths(runs) -> list[int]:
    return [len(e) for _, e in runs]


def plant_targets(seed: int, F: np.ndarray, lengths, config: dict,
                  block: int = 8192) -> np.ndarray:
    """(n, t) targets of a linear model on the delayed features, every
    target responsive at the configured SNR, with drift and noise,
    normalised per column (as ``gen.subject`` plants the parcel cell's),
    the delayed rows made and multiplied in blocks on the device."""
    import jax
    import jax.numpy as jnp

    d = config["data"]
    n, t, lags = F.shape[0], config["t"], config["lags"]
    p = lags * F.shape[1]
    k = gen.key(seed, 30)
    kw, kn, kd = jax.random.split(k, 3)
    W = jax.random.normal(kw, (p, t), jnp.float32) / np.sqrt(p)
    scale = float(np.sqrt(np.mean(np.square(F, dtype=np.float64))))
    X = rb.delays(F, lengths, lags)
    sig = np.concatenate([np.asarray(jnp.matmul(
        jnp.asarray(X[lo:lo + block]) / scale, W, precision="highest"))
        for lo in range(0, n, block)])
    del X
    noise = np.asarray(jax.random.normal(kn, (n, t), jnp.float32))
    phase = np.asarray(jax.random.uniform(kd, (1, t))) * 2 * np.pi
    tt = np.arange(n, dtype=np.float32)[:, None] * d["tr_seconds"]
    Y = (sig * d["snr_responsive"] + noise
         + d["drift_amp"] * np.sin(2 * np.pi * 0.003 * tt + phase))
    return ((Y - Y.mean(0)) / (Y.std(0) + 1e-6)).astype(np.float32)


def make_subject(seed: int, config: dict, traffic: dict):
    """Weights, transcripts, the reference's per-TR features and the
    seconds those features took."""
    params = rb.weights(seed, config)
    runs = rb.transcripts(seed, config["n"], traffic, config["vocab_size"])
    t0 = time.perf_counter()
    F = rb.features(params, config, runs)
    return params, runs, F, time.perf_counter() - t0


def feature_checks(F: np.ndarray, F_ref: np.ndarray, limits: dict
                   ) -> list[Check]:
    """‖F − F_ref‖/‖F_ref‖ over all rows."""
    F, F_ref = np.asarray(F, np.float64), np.asarray(F_ref, np.float64)
    rel = float(np.linalg.norm(F - F_ref) / np.linalg.norm(F_ref))
    return [Check("features_rel_err", rel, limits["features_rel_err"])]


#: Packed rows, of 64 SSD chunks each, whose runs are checked layer by layer.
LAYER_ROWS = 2


def _padded(tokens, Q: int) -> int:
    return max(Q, -(-len(tokens) // Q) * Q)


def layer_sample(runs, Q: int, rows: int = LAYER_ROWS) -> list[list[int]]:
    """The runs of the first ``rows`` rows of 64 SSD chunks, packed in
    order as the program packs them (at least one run a row)."""
    groups, used = [[]], 0
    for i, (t, _) in enumerate(runs):
        L = _padded(t, Q)
        if groups[-1] and used + L > 64 * Q:
            if len(groups) == rows:
                break
            groups.append([])
            used = 0
        groups[-1].append(i)
        used += L
    return groups


def _merge(acc: dict | None, new: dict) -> dict:
    if acc is None:
        return new
    return {"err2": [a + b for a, b in zip(acc["err2"], new["err2"])],
            "ref2": [a + b for a, b in zip(acc["ref2"], new["ref2"])],
            "row": [max(a, b) for a, b in zip(acc["row"], new["row"])],
            "differ": acc["differ"] + new["differ"],
            "pairs": acc["pairs"] + new["pairs"]}


def layer_checks(acc: dict, limits: dict) -> list[Check]:
    """The worst layer's ‖ΔΔ‖/‖Δ_ref‖ (Δ: a layer's update of the stream),
    the worst token's in any layer, and the share of (token, layer) pairs
    whose picks are not the reference's own."""
    rel = max(float(np.sqrt(e / r)) for e, r in zip(acc["err2"], acc["ref2"]))
    log(f"bench: layer by layer (embedding first): rel "
        f"{[float(np.sqrt(e / r)) for e, r in zip(acc['err2'], acc['ref2'])]}"
        f", row {acc['row']}, picks differ {acc['differ']} of "
        f"{acc['pairs']}")
    return [Check("layer_rel_err", rel, limits["layer_rel_err"]),
            Check("layer_row_max_err", max(acc["row"]),
                  limits["layer_row_max_err"]),
            Check("route_mismatch_share", acc["differ"] / acc["pairs"],
                  limits["route_mismatch_share"])]


def program_layers(extractor, runs, config: dict) -> dict:
    """Every layer of the sampled runs through the program
    (``HybridLM.features(every_layer=True)`` over the runs packed as the
    program packs them), checked by ``reference_backbone.layer_errors``."""
    import jax
    import jax.numpy as jnp

    from repro.encoding import backbone

    Q = extractor.cfg.ssm.chunk
    fn = jax.jit(extractor.model.features,
                 static_argnames=("attn_span", "every_layer"))
    params = extractor.params()
    acc = None
    for group in layer_sample(runs, Q):
        pk = backbone.pack([backbone.Transcript(*runs[i]) for i in group], Q,
                           1, extractor.lags)
        assert pk.tokens.shape[0] == 1, pk.tokens.shape
        h, _, ins, picks = fn(params, pk.tokens, pk.pos, attn_span=pk.span,
                              every_layer=True)
        lens = np.array([_padded(runs[i][0], Q) for i in group])
        offs = np.cumsum(lens) - lens
        idx = jnp.asarray(np.minimum(offs[:, None] + np.arange(lens.max()),
                                     (offs + lens - 1)[:, None]))
        states = [jnp.take(ins[i, 0], idx, axis=0) for i in range(len(ins))]
        states.append(jnp.take(h[0], idx, axis=0))
        pr = [jnp.take(picks[i, 0], idx, axis=0) for i in range(len(picks))]
        del h, ins, picks
        acc = _merge(acc, rb.layer_errors(
            params, config, jnp.take(jnp.asarray(pk.tokens[0]), idx), states,
            pr, [len(runs[i][0]) for i in group]))
        del states, pr
    extractor.release()
    return acc


def control_layers(params, runs, config: dict) -> dict:
    """``program_layers`` with the control's chain in the program's place."""
    Q = config["mamba_chunk_size"]
    acc = None
    for group in layer_sample(runs, Q):
        tokens = np.zeros((len(group), max(_padded(runs[i][0], Q)
                                           for i in group)), np.int32)
        for r, i in enumerate(group):
            tokens[r, :len(runs[i][0])] = runs[i][0]
        states, picks = rb.layer_states(params, config, tokens, control=True)
        acc = _merge(acc, rb.layer_errors(params, config, tokens, states,
                                          picks, [len(runs[i][0])
                                                  for i in group]))
    return acc


def ridge_checks(config: dict, limits: dict, X: np.ndarray, Y: np.ndarray,
                 bundles: list[str], control: str | None = None
                 ) -> list[Check]:
    """The parcel cell's checks of each bundle against the reference ridge
    on (X, Y) at the configuration's precision.  With ``control`` (a
    precision) the reference's own refit at that precision, at the λ the
    reference chose, stands in for the bundles."""
    import jax.numpy as jnp

    from bench.drivers.fit import read_bundle

    t0 = time.perf_counter()
    prec, lams = config["precision"], config["lambdas"]
    jitter = config["program"]["jitter"]
    Xd, Yd = jnp.asarray(X), jnp.asarray(Y)
    stats = reference.fold_stats(Xd, Yd, config["k"], prec)
    curve = reference.ridge_cv_curve(Xd, Yd, stats, lams, jitter, prec)
    G, C = reference.summed(stats)
    del stats
    best = lams[int(np.argmax(curve))]
    if control is None:
        answers = [read_bundle(b) for b in bundles]
    else:
        Gc, Cc = reference.summed(reference.fold_stats(Xd, Yd, config["k"],
                                                       control))
        answers = [{"lambda": best, "curve": curve,
                    "W": np.asarray(reference.ridge_refit(
                        Gc, Cc, best, jitter, control, check=False))}]
        del Gc, Cc
    del Xd, Yd
    refits, mismatch, w_err, resid = {}, 0.0, 0.0, 0.0
    for a in answers:
        lam = a["lambda"]
        mismatch = max(mismatch, float(lam != best))
        if lam not in refits:
            refits[lam] = np.asarray(reference.ridge_refit(
                G, C, lam, jitter, prec), np.float64)
        W_ref = refits[lam]
        w_err = max(w_err, float(np.linalg.norm(a["W"] - W_ref)
                                 / np.linalg.norm(W_ref)))
        resid = max(resid, reference.normal_residual(G, C, lam + jitter,
                                                     a["W"]))
    log(f"bench: reference ridge {time.perf_counter() - t0:.1f} s; curve "
        f"{np.round(curve, 7).tolist()}; curve gap "
        f"{max(float(np.max(np.abs(a['curve'] - curve))) for a in answers)!r}")
    return [Check("lambda_mismatch", mismatch, limits["lambda_mismatch"]),
            Check("weights_rel_err", w_err, limits["weights_rel_err"]),
            Check("normal_eq_residual", resid, limits["normal_eq_residual"])]


def control_checks(cell, seed: int, seconds: float,
                   precision: str) -> list[Check]:
    """The checks with the reference one step below the configuration's
    precision in the program's place (``seconds`` plays no part): its
    features and its layers with bf16 SSD state, norms and router logits,
    and its ridge on its own delayed features at ``precision``."""
    config = cell.config
    params, runs, F_ref, _ = make_subject(seed, config, cell.traffic)
    F_ctl = rb.features(params, config, runs, control=True)
    layers = control_layers(params, runs, config)
    del params
    lengths = _run_lengths(runs)
    Y = plant_targets(seed, F_ref, lengths, config)
    return (feature_checks(F_ctl, F_ref, cell.limits)
            + layer_checks(layers, cell.limits)
            + ridge_checks(config, cell.limits,
                           rb.delays(F_ref, lengths, config["lags"]), Y, [],
                           control=precision))


def tiny(cell):
    """The cell at a size the CPU runs in a test: the same layer pattern,
    hidden 32, 8 experts of which 4 held, top-2, runs of 96 TRs."""
    cfg = dict(cell.config, hidden_size=32, mamba_n_heads=8, mamba_d_head=8,
               mamba_d_state=8, mamba_chunk_size=8, num_attention_heads=4,
               num_key_value_heads=2, router_experts=8, num_local_experts=4,
               num_experts_per_tok=2, intermediate_size=16,
               shared_intermediate_size=32, vocab_size=97, n=1536, p=128,
               t=32)
    cfg["program"] = dict(cfg["program"], chunk_rows=384)
    tr = dict(cell.traffic, run_trs=96)
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def faults(kind: str) -> dict:
    """Planted faults: the residual multiplier left out; the gated norm
    per head; capacity dropping at 1.25; a softmax over all the experts
    before the top-k; lags not reset at a run boundary."""
    import jax
    import jax.numpy as jnp

    from repro.encoding import backbone
    from repro.models import hybrid, moe, ssm

    gated_norm, route = ssm._gated_norm, moe.route_topk

    def per_head(p, y, z, cfg):
        return gated_norm(p, y, z, dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, norm="head")))

    def capacity(logits, k):
        gates, idx = route(logits, k)
        T, E = logits.shape
        cap = int(np.ceil(1.25 * T * k / E))
        onehot = jax.nn.one_hot(idx.T.reshape(-1), E, dtype=jnp.int32)
        place = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        keep = (place < cap).reshape(k, T).T
        return jnp.where(keep, gates, 0.0), idx

    def softmax_all(logits, k):
        return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)

    return {"residual_multiplier_left_out": (
                hybrid, "_branch", lambda h, y, cfg: h + y),
            "gated_norm_per_head": (ssm, "_gated_norm", per_head),
            "capacity_dropping": (moe, "route_topk", capacity),
            "softmax_before_top_k": (moe, "route_topk", softmax_all),
            "lags_not_reset": (backbone, "delay_rows",
                               _keep(backbone.delay_rows))}


def _keep(delay_rows):
    """``delay_rows`` with every TR's position taken as past the lags: no
    lag is zeroed at a run's start (the buffer's leading halo still is)."""
    import jax.numpy as jnp

    def no_reset(F, start, pos, lags, rows):
        return delay_rows(F, start, jnp.full_like(pos, lags + (1 << 20)),
                          lags, rows)
    return no_reset


class _PhaseMemory:
    """Device memory at the end of the fit's phases, read from a span
    tracer installed for one fit of the set-up."""

    PHASES = ("fit.extract", "fit.stats", "fit.eigh", "fit.solve")

    def __init__(self):
        from repro import obs

        outer = self
        self.read = {}

        class Tracer(obs.Tracer):
            def _record(self, name, *a, **k):
                super()._record(name, *a, **k)
                if name in outer.PHASES:
                    import jax
                    st = jax.local_devices()[0].memory_stats() or {}
                    outer.read[name] = (int(st.get("bytes_in_use", 0)),
                                        int(st.get("peak_bytes_in_use", 0)))
        self.tracer = Tracer()


def fit_once(extractor, store_dir: str, bundle_dir: str, cfg) -> dict:
    """One subject from store open to committed bundle."""
    from repro import obs
    from repro.data.store import RunStore
    from repro.encoding import BrainEncoder
    from repro.encoding.backbone import BackboneSource

    pairs = obs.get_metrics().counter("moe_routed_pairs")
    p0 = pairs.value
    t0 = time.perf_counter()
    src = BackboneSource(extractor, RunStore.open(store_dir))
    enc = BrainEncoder(cfg).fit_chunks(src)
    enc.save(bundle_dir, overwrite=True)
    t1 = time.perf_counter()
    return {"t0": t0, "dur_s": t1 - t0, "bundle": bundle_dir,
            "method": enc.report_.decision.method,
            "run_tokens": [len(t.tokens) for t in src.transcripts],
            "moe_calls": extractor.last["batches"] * extractor.cfg.n_layers,
            "pairs": float(pairs.value - p0)}


def run(h: Harness, seed: int, seconds: float, budget: int) -> None:
    from repro import obs
    from repro.encoding import backbone

    rec = h.rec
    config, traffic = rec.config, rec.traffic
    cfg = _encoder_config(config, budget)
    t0 = time.perf_counter()
    params, runs, F_ref, ref_s = make_subject(seed, config, traffic)
    h.t_start += ref_s          # the reference's seconds are not set-up
    lengths = _run_lengths(runs)
    t_ref = time.perf_counter()
    Y = plant_targets(seed, F_ref, lengths, config)
    offs = np.cumsum([0] + lengths)
    store_dir = h.path("store")
    backbone.write_subject(
        store_dir, [backbone.Transcript(tokens=t, tr_end=e) for t, e in runs],
        [Y[a:b] for a, b in zip(offs[:-1], offs[1:])], n_folds=config["k"])
    extractor = backbone.Extractor(model_config(config), params,
                                   lags=config["lags"], budget=budget)
    del params
    gc.collect()
    t1 = time.perf_counter()
    probe = _PhaseMemory()
    for i in range(rec.traffic["warmup_fits"]):
        obs.install(probe.tracer if i == 0 else None)
        try:
            fit_once(extractor, store_dir, h.path(f"warm-{i}"), cfg)
        finally:
            obs.uninstall()
    log(f"bench: set-up: weights and reference features {t_ref - t0:.1f} "
        f"s (the features {ref_s:.1f} s, not counted), targets, store and "
        f"extractor {t1 - t_ref:.1f} s, warm-up fits "
        f"{time.perf_counter() - t1:.1f} s; weights "
        f"{extractor.weight_bytes} B, resident {extractor.resident}; "
        f"device bytes (in use, peak) after each phase of a fit: "
        f"{probe.read}")

    with h.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            rec.fits.append(fit_once(extractor, store_dir,
                                     h.path(f"bundle-{len(rec.fits)}"), cfg))
    rec.attempted = len(rec.fits)
    for f in rec.fits:                  # as the parcel cell's driver keeps them
        for name in ("fit.stats", "fit.eigh"):
            spans = h.span_events(f["t0"], f["t0"] + f["dur_s"], name)
            if spans:
                f[name] = sum(s["dur"] for s in spans)
    rec.memory_peak_bytes = h.memory_peak()
    from repro.data.store import RunStore
    src = backbone.BackboneSource(extractor, RunStore.open(store_dir))
    F = np.asarray(src.features())[config["lags"]:config["lags"] + len(Y)]
    del src
    layers = program_layers(extractor, runs, config)
    del extractor
    gc.collect()
    rec.checks = feature_checks(F, F_ref, rec.limits)
    rec.checks += layer_checks(layers, rec.limits)
    rec.checks += ridge_checks(config, rec.limits, rb.delays(F, lengths,
                                                           config["lags"]),
                               Y, [f["bundle"] for f in rec.fits])
    bad = [f for f in rec.fits if f["method"] != config["program"]["method"]]
    if bad:
        rec.checks.append(Check("fits_off_configured_path", len(bad), 0))
