"""Brain-encoding driver — the paper's full pipeline, end to end.

stimulus features (synthetic VGG16-shaped features, or a feature
backbone's hidden states) → ``BrainEncoder`` (solver picked by
complexity-driven dispatch: distributed B-MOR on a multi-device mesh,
mutualised RidgeCV otherwise) → Pearson-r encoding map + null permutation
control.

``python -m repro.launch.encode --smoke`` runs the paper-shaped synthetic
pipeline on CPU; ``--backbone granite-4.0-h-small --smoke --store DIR``
streams a backbone's delayed features from transcripts through the same
fit (``repro.encoding.backbone``).
"""
from __future__ import annotations

import argparse


def _run_store_mode(args) -> None:
    """Out-of-core path: materialise a synthetic subject once, stream it.

    ``--store DIR`` either opens an existing ``RunStore`` or writes one
    (CNeuroMod-shaped synthetic runs via ``materialize_synthetic``), then
    fits through ``BrainEncoder.fit(store=...)`` under ``--budget-mb`` —
    dispatch pins the streamed fold-statistics path whenever the resident
    estimate exceeds the budget, sharding the accumulation over the local
    devices.
    """
    import os

    import jax
    from repro.data import fmri
    from repro.data.store import MANIFEST_NAME, RunStore
    from repro.encoding import BrainEncoder, EncoderConfig
    from repro.encoding.dispatch import materialized_fit_bytes

    if os.path.exists(os.path.join(args.store, MANIFEST_NAME)):
        store = RunStore.open(args.store)
        print(f"opened store {args.store}: shape {store.shape}")
    else:
        spec = fmri.SubjectSpec(n=args.n, p=128, t=args.targets)
        store = RunStore.create(args.store)
        store.materialize_synthetic(
            spec, rows_per_run=max(1, min(spec.n, 4 * args.chunk_rows)))
        store = RunStore.open(args.store)
        print(f"materialised synthetic subject into {args.store}: "
              f"shape {store.shape}")

    n, p, t = store.shape
    budget = int(args.budget_mb * 2**20)
    enc = BrainEncoder(EncoderConfig(device_memory_budget=budget,
                                     chunk_rows=args.chunk_rows,
                                     prefetch=args.prefetch))
    enc.fit(store=store)
    d = enc.report_.decision
    resident = materialized_fit_bytes(n, p, t, enc.config.n_folds)
    print(f"in-memory fit estimate {resident / 2**20:.1f} MB vs budget "
          f"{args.budget_mb:.1f} MB on {jax.device_count()} device(s)")
    print(f"dispatch: solver={d.solver} method={d.method} "
          f"data_shards={d.data_shards} ({d.rationale})")
    if enc.stream_stats_ is not None:
        ss = enc.stream_stats_
        print(f"stream: prefetch={'on' if ss['prefetch'] else 'off'} "
              f"chunks={ss['chunks']} "
              f"staged={ss['bytes_staged'] / 2**20:.1f} MB "
              f"read_stall={ss['read_stall_s']:.2f}s "
              f"compute_stall={ss['compute_stall_s']:.2f}s "
              f"accumulation compiles={ss['compile_count']} "
              f"[{ss['schema']}]")
    print(f"{enc.report_.solver_label} fit: λ = {enc.report_.best_lambda}, "
          f"CV scores {enc.report_.cv_scores.round(4)}")
    if args.save_bundle:
        _save_bundle_with_report(enc, args.save_bundle,
                                 provenance={"source": "run_store",
                                             "store": args.store,
                                             "shape": list(store.shape)})


def _run_backbone_mode(args) -> None:
    """Backbone features through the normal streamed fit.

    ``--store DIR`` opens a subject written by
    ``repro.encoding.backbone.write_subject`` (targets plus one transcript
    per run), or first writes a synthetic one: random transcripts, and
    targets planted on the backbone's own delayed features, in runs of
    480 TRs.  The fit then
    streams ``BackboneSource`` chunks through ``BrainEncoder.fit_chunks``.
    """
    import os

    import jax
    import numpy as np
    from repro import configs
    from repro.data.store import MANIFEST_NAME, RunStore
    from repro.encoding import BrainEncoder, EncoderConfig
    from repro.encoding import backbone as bb
    from repro.models import build_model
    from repro.models.reference.granite import delays

    cfg = configs.get_config(args.backbone)
    if args.backbone not in configs.BACKBONE_IDS:
        raise SystemExit(f"{args.backbone!r} is not a feature backbone; "
                         f"known: {configs.BACKBONE_IDS}")
    if args.smoke:
        cfg = configs.tiny_backbone(cfg)
    params = build_model(cfg).init(jax.random.PRNGKey(1))
    budget = int(args.budget_mb * 2**20)
    extractor = bb.Extractor(cfg, params, budget=budget)
    if not os.path.exists(os.path.join(args.store, MANIFEST_NAME)):
        rng = np.random.default_rng(0)
        runs = [min(480, args.n - lo) for lo in range(0, args.n, 480)]
        scripts = []
        for trs in runs:
            per = np.where(rng.random(trs) < 0.25, 0,
                           1 + rng.poisson(3.0, trs))
            end = np.cumsum(per)
            scripts.append(bb.Transcript(
                tokens=rng.integers(0, cfg.vocab, int(end[-1]),
                                    dtype=np.int32),
                tr_end=(end - 1).astype(np.int32)))
        F = np.asarray(extractor.extract(scripts, args.n))[4:4 + args.n]
        X = delays(F, runs, extractor.lags)
        W = rng.standard_normal((X.shape[1], args.targets)) / np.sqrt(
            X.shape[1])
        Y = X / X.std() @ W * 2.0 + rng.standard_normal((args.n,
                                                          args.targets))
        Y = ((Y - Y.mean(0)) / Y.std(0)).astype(np.float32)
        offs = np.cumsum([0] + runs)
        bb.write_subject(args.store, scripts,
                         [Y[a:b] for a, b in zip(offs[:-1], offs[1:])],
                         n_folds=5)
        print(f"wrote a synthetic subject of {len(runs)} runs to "
              f"{args.store}")
    src = bb.BackboneSource(extractor, RunStore.open(args.store))
    enc = BrainEncoder(EncoderConfig(device_memory_budget=budget,
                                     chunk_rows=args.chunk_rows))
    enc.fit_chunks(src)
    d = enc.report_.decision
    print(f"backbone features from {cfg.name}: n={src.shape[0]} "
          f"p={src.shape[1]} t={src.shape[2]} ({extractor.last['tokens']} "
          f"tokens in {extractor.last['batches']} batches)")
    print(f"dispatch: solver={d.solver} method={d.method} "
          f"use_pallas={d.use_pallas}")
    print(f"{enc.report_.solver_label} fit: λ = {enc.report_.best_lambda}, "
          f"CV scores {enc.report_.cv_scores.round(4)}")
    if args.save_bundle:
        _save_bundle_with_report(enc, args.save_bundle,
                                 provenance={"source": "backbone",
                                             "backbone": cfg.name,
                                             "store": args.store})


def _save_bundle_with_report(encoder, bundle_dir: str,
                             provenance: dict | None = None) -> None:
    """Persist the fitted encoder + machine-readable run provenance.

    The bundle directory gets the ``EncoderBundle`` payload; ``report.json``
    (``EncodingReport.to_json``) rides next to it so downstream tooling can
    read solver/λ/CV provenance without touching the arrays.
    """
    import os

    path = encoder.save(bundle_dir, overwrite=True, provenance=provenance)
    with open(os.path.join(path, "report.json"), "w") as f:
        f.write(encoder.report_.to_json())
    print(f"bundle saved → {path} (report.json alongside)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backbone", default="vgg16",
                    help="'vgg16' for the paper's feature shape, or a "
                         "feature backbone (configs.BACKBONE_IDS) with "
                         "--store")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n", type=int, default=512, help="time samples")
    ap.add_argument("--targets", type=int, default=256)
    ap.add_argument("--solver", default="auto",
                    help="auto|ridge|mor|bmor|bmor_dual|banded")
    ap.add_argument("--target-shards", type=int, default=None,
                    help="pin the target-batch shard count (default: dispatch)")
    ap.add_argument("--store", default=None,
                    help="out-of-core mode: RunStore directory (materialised "
                         "with synthetic runs on first use, then streamed)")
    ap.add_argument("--chunk-rows", type=int, default=8192,
                    help="row-batch size of the streaming accumulation")
    ap.add_argument("--prefetch", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="overlap the next chunk's disk read with the "
                         "current accumulation (--no-prefetch for the "
                         "serial A/B; results are bit-identical)")
    ap.add_argument("--budget-mb", type=float, default=64.0,
                    help="device-memory budget (MB) for --store dispatch")
    ap.add_argument("--save-bundle", default=None,
                    help="persist the fitted encoder as an EncoderBundle "
                         "directory (+ report.json run provenance) for the "
                         "serving subsystem")
    from repro.launch.obscli import add_obs_args, obs_session
    add_obs_args(ap)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    with obs_session(args):
        _run(args)


def _run(args) -> None:
    if args.backbone != "vgg16":
        if args.store is None:
            raise SystemExit("a backbone's features stream from a store: "
                             "pass --store DIR")
        _run_backbone_mode(args)
        return
    if args.store is not None:
        _run_store_mode(args)
        return

    import jax
    import numpy as np
    from repro.data import fmri
    from repro.encoding import EncoderConfig, pipeline

    n, t = args.n, args.targets
    key = jax.random.PRNGKey(0)

    # 1. Stimulus features X (the paper's shape; backbones go through
    # --store, _run_backbone_mode).
    spec = fmri.SubjectSpec(n=n, p=128, t=t)
    X, Y, mask = fmri.generate(key, spec)
    print(f"synthetic VGG16-shaped features: X{X.shape} Y{Y.shape}")

    # 2-4. 90/10 split → standardize (train-fitted) → fit → evaluate, through
    # the unified estimator API: no mesh/device_put boilerplate here, the
    # dispatch layer picks ridge vs (dual) B-MOR from the problem shape and
    # jax.device_count() (§3 cost model).
    enc_cfg = EncoderConfig(solver=args.solver,
                            target_shards=args.target_shards)
    state = pipeline.run(X, Y, enc_cfg, detrend_targets=False, n_perms=5)
    report, ev = state.report, state.evaluation

    d = report.decision
    print(f"dispatch: solver={d.solver} mesh={d.data_shards}x"
          f"{d.target_shards} ({d.rationale})")
    print(f"{report.solver_label} fit: per-batch λ = {report.best_lambda}")

    if args.save_bundle:
        _save_bundle_with_report(
            state.encoder, args.save_bundle,
            provenance={"source": "pipeline", "backbone": args.backbone,
                        "n": args.n, "targets": args.targets})

    r_np = ev.pearson_r
    m = np.asarray(mask)
    print(f"test Pearson r: responsive targets mean={r_np[m].mean():.3f}  "
          f"non-responsive mean={r_np[~m].mean():.3f}")
    ok = r_np[m].mean() > 5 * ev.null_abs_r
    print(f"null permutation |r|: mean={ev.null_abs_r:.4f} "
          + ("(aligned encoding is significant, paper §4.2)" if ok else
             "(WARNING: responsive targets do not clear the null floor)"))


if __name__ == "__main__":
    main()
