"""Find the highest open-loop rate a serving cell sustains (run once, by
hand, to fix the rate in its traffic file).

    python3 -m bench.sweep --config wholebrain_q4 --traffic predict \\
        --seed 5 --seconds 15 --rates 2000,4000,6000

Set-up as in a run; then one open-loop window per rate, printing one JSON
line each: latency percentiles, rows/s completed, failures, and whether
the backlog grew (the p95 of the window's last quarter against its first).
"""
from __future__ import annotations

import argparse
import json
import time

from bench import arrivals, run, spec
from bench.drivers import serve
from bench.harness import Harness, RunRecord


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.assemble(f"{args.config}.{args.traffic}", args.config,
                         args.traffic)
    run.setup_jax()
    devs = run.find_chips(cell.chips)
    cfg = cell.config
    rec = RunRecord(cell=cell.name, kind="open_loop", config=cfg,
                    traffic=cell.traffic, limits=cell.limits,
                    device_kind=devs[0].device_kind)
    h = Harness(rec, trace=False, workdir=run.WORK_DIR + "/sweep",
                t_start=t_start)
    try:
        fleet = serve.Fleet(cfg, serve.write_bundle(h, args.seed, cfg))
        xpool, ypool = serve.pools(rec, args.seed)
        serve.warm_up(fleet, cfg, xpool, ypool, False)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            rec.traffic = dict(cell.traffic, rate_rows_per_s=rate)
            rec.requests, rec.failed = [], 0
            reqs = arrivals.open_loop(rec.traffic, cfg, args.seed + i,
                                      args.seconds)
            recorder = serve.Recorder([], cfg, args.seed, rec)
            t0 = time.perf_counter()
            serve.open_loop(h, fleet, reqs, xpool, ypool, recorder,
                            args.seconds)
            wall = time.perf_counter() - t0
            done = sorted(rec.requests, key=lambda r: r["due"])
            lat = [(r["done"] - r["due"]) * 1e3 for r in done if r["ok"]]
            q = max(1, len(done) // 4)
            first = [(r["done"] - r["due"]) * 1e3 for r in done[:q]]
            last = [(r["done"] - r["due"]) * 1e3 for r in done[-q:]]
            print(json.dumps({
                "rate_rows_per_s": rate, "requests": len(done),
                "failed": rec.failed, "wall_s": wall,
                "rows_per_s_done": sum(r["rows"] for r in done) / wall,
                "p50_ms": arrivals.percentile(lat, 50),
                "p95_ms": arrivals.percentile(lat, 95),
                "p99_ms": arrivals.percentile(lat, 99),
                "p95_first_quarter_ms": arrivals.percentile(first, 95),
                "p95_last_quarter_ms": arrivals.percentile(last, 95)}),
                flush=True)
    finally:
        h.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
