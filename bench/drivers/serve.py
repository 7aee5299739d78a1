"""Serving from a fleet of whole-brain bundles: open-loop arrivals or
closed-loop clients through ``FleetFrontend`` → ``EncoderService`` →
``EncoderRegistry``.

Set-up makes the encoder from the seed on the device, writes it through
the program's bundle writer, registers it under every subject's name, and
flies one wave of every shape the window uses for every subject (which
loads each subject's copy onto the device and compiles the wave
program).  The window
serves the traffic mix.  Afterwards a sample of the answers, drawn from
the seed, is compared on sampled target columns with a float64
reference on the host: the predictions and, for scored requests, the
Pearson r.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import queue
import threading
import time

import numpy as np

from bench import arrivals, gen, reference
from bench.harness import Check, Harness, RunRecord, log

KINDS = ("open_loop", "closed_loop")


def _name(s: int) -> str:
    return f"sub-{s + 1:02d}"


def write_bundle(h: Harness, seed: int, config: dict) -> str:
    """The served encoder's bundle, through the program's writer.

    Every resident subject is served from this one bundle: the registry
    loads a device copy per subject name, so device memory and wave work
    are those of ``subjects`` distinct quarter-bundles, while set-up
    writes one bundle (4.34 GB at the cell's size) instead of one per
    subject."""
    from repro.encoding import BrainEncoder, DispatchDecision, EncoderConfig
    from repro.encoding.estimator import EncodingReport
    from repro.encoding.pipeline import Standardizer

    lams = tuple(config["lambdas"])
    W, mu_x, sd_x, mu_y, sd_y = gen.encoder_for(seed, config)
    enc = BrainEncoder(EncoderConfig(n_folds=config["k"], lambdas=lams))
    enc.report_ = EncodingReport(
        weights=W, best_lambda=np.asarray([lams[len(lams) // 2]]),
        cv_scores=np.zeros((1, len(lams))), lambdas=lams,
        decision=DispatchDecision(
            solver="ridge", method="chunked", data_shards=1,
            target_shards=1, predicted_cost=0.0,
            rationale="weights made from the benchmark's seed"))
    enc.standardizer_ = Standardizer(
        mu_x=np.asarray(mu_x), sd_x=np.asarray(sd_x),
        mu_y=np.asarray(mu_y), sd_y=np.asarray(sd_y))
    return enc.save(h.path("bundle"))


class Fleet:
    """The system under test, as a deployment builds it."""

    def __init__(self, config: dict, path: str):
        from repro.serving_encoders import EncoderRegistry, EncoderService
        from repro.serving_encoders.fleet import FleetFrontend

        serving = config["serving"]
        self.registry = EncoderRegistry(wave_rows=max(serving["wave_buckets"]))
        for s in range(config["subjects"]):
            self.registry.add(_name(s), path)
        self.service = EncoderService(self.registry,
                                      wave_buckets=serving["wave_buckets"],
                                      score_slots=serving["score_slots"])
        self.frontend = FleetFrontend(
            self.service, max_pending_rows=serving["max_pending_rows"])


def _request(r: arrivals.Request, xpool, ypool):
    from repro.serving_encoders import PredictRequest

    rows = slice(r.offset, r.offset + r.rows)
    return PredictRequest(model=_name(r.subject), features=xpool[rows],
                          targets=ypool[rows] if r.scored else None,
                          tenant=f"client-{r.client}")


def warm_up(fleet: Fleet, config: dict, xpool, ypool, scored: bool) -> None:
    """One wave of every shape, for every subject."""
    for s in range(config["subjects"]):
        for rows in config["serving"]["wave_buckets"]:
            r = arrivals.Request(-1, 0.0, s, rows, 0, scored)
            fleet.frontend.submit(_request(r, xpool, ypool))
            fleet.frontend.flush()


class Recorder:
    """Keeps each request's times and, for the sampled ones, the answer
    on their compared columns."""

    def __init__(self, sample, config, seed, rec):
        self.rec = rec
        self.cols = {i: arrivals.check_columns(
            config["t"], rec.traffic["check_columns"], seed, i)
            for i in sample}
        self.answers: dict[int, dict] = {}

    def record(self, r, result, due, sent, flush_start, done) -> None:
        ok = result is not None and result.error is None \
            and result.predictions is not None
        self.rec.requests.append({
            "index": r.index, "rows": r.rows, "scored": r.scored,
            "due": due, "sent": sent, "flush_start": flush_start,
            "done": done, "ok": ok})
        if not ok:
            self.rec.failed += 1
        if r.index in self.cols and ok:
            c = self.cols[r.index]
            self.answers[r.index] = {
                "pred": np.array(result.predictions[:, c]),
                "r": (None if result.pearson_r is None
                      else np.array(result.pearson_r[c]))}


def _serve(fleet, batch, xpool, ypool, recorder, due_of) -> None:
    """Admit a batch, flush it, record every request."""
    from repro.serving_encoders import ServiceError

    admitted = []
    for r, sent in batch:
        try:
            fleet.frontend.submit(_request(r, xpool, ypool))
            admitted.append((r, sent))
        except ServiceError:
            recorder.record(r, None, due_of(r), sent, math.nan, math.nan)
    flush_start = time.perf_counter()
    results = fleet.frontend.flush()
    done = time.perf_counter()
    for (r, sent), res in zip(admitted, results):
        recorder.record(r, res, due_of(r), sent, flush_start, done)


def open_loop(h, fleet, reqs, xpool, ypool, recorder, seconds) -> None:
    """Arrivals on a schedule from a generator thread; the server admits
    whatever has arrived and flushes it as one batch."""
    q: queue.Queue = queue.Queue()
    t0 = time.perf_counter() + 0.01

    def generator():
        for r in reqs:
            delay = t0 + r.due_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            q.put((r, time.perf_counter()))
        q.put(None)

    gen_thread = threading.Thread(target=generator, name="bench-generator")
    gen_thread.start()
    try:
        finished = False
        while not finished:
            batch = [q.get()]
            while True:
                try:
                    batch.append(q.get_nowait())
                except queue.Empty:
                    break
            if batch[-1] is None:
                finished = True
                batch.pop()
            if batch:
                _serve(fleet, batch, xpool, ypool, recorder,
                       lambda r: t0 + r.due_s)
    finally:
        gen_thread.join()


def closed_loop(h, fleet, reqs, xpool, ypool, recorder, seconds) -> None:
    """Each client sends its next request when its last one is answered;
    no batch starts after ``seconds``."""
    clients = h.rec.traffic["clients"]
    t0 = time.perf_counter()
    nxt = 0
    while time.perf_counter() - t0 < seconds and nxt + clients <= len(reqs):
        now = time.perf_counter()
        batch = [(r, now) for r in reqs[nxt:nxt + clients]]
        nxt += clients
        _serve(fleet, batch, xpool, ypool, recorder, lambda r: now)


def compare(config, limits, seed, reqs, answers, cols, xpool, ypool,
            control: str | None = None) -> list[Check]:
    """The sampled answers against the float64 reference.  With
    ``control`` the reference at that device precision stands in for
    the program's answers."""
    pred_err = r_err = 0.0
    unanswered = sum(1 for i in cols if i not in answers and control is None)
    W, mu_x, sd_x, mu_y, sd_y = gen.encoder_for(seed, config)
    mx, sx = np.asarray(mu_x), np.asarray(sd_x)
    my, sy = np.asarray(mu_y), np.asarray(sd_y)
    for i in sorted(cols):
        r, c = reqs[i], cols[i]
        rows = slice(r.offset, r.offset + r.rows)
        Wc = np.asarray(W[:, c])
        ref = reference.predictions(xpool[rows], Wc, mx, sx, my[c], sy[c])
        if control is not None:
            got = np.asarray(reference.predictions_device(
                xpool[rows], Wc, mx, sx, my[c], sy[c], precision=control),
                np.float64)
            got_r = (reference.pearson(ypool[rows][:, c], got) if r.scored
                     else None)
        elif i in answers:
            got, got_r = answers[i]["pred"], answers[i]["r"]
        else:
            continue
        pred_err = max(pred_err, float(np.linalg.norm(got - ref)
                                       / np.linalg.norm(ref)))
        if r.scored:
            r_ref = reference.pearson(ypool[rows][:, c], ref)
            r_err = max(r_err, float(np.max(np.abs(got_r - r_ref))))
    del W
    checks = [Check("unanswered_sampled", unanswered, 0),
              Check("pred_rel_err", pred_err, limits["pred_rel_err"])]
    if "pearson_abs_err" in limits:
        checks.append(Check("pearson_abs_err", r_err,
                            limits["pearson_abs_err"]))
    return checks


def control_checks(cell, seed: int, seconds: float,
                   precision: str) -> list[Check]:
    """The checks with the reference at ``precision`` in the program's
    place, on the requests a run of ``seconds`` would compare."""
    cfg = cell.config
    rec = RunRecord(cell=cell.name, kind=cell.traffic["kind"], config=cfg,
                    traffic=cell.traffic, limits=cell.limits,
                    device_kind="")
    reqs = schedule(rec, seed, seconds)
    want = cell.traffic["check_requests"]
    sample = (arrivals.check_sample([r.rows for r in reqs], want, seed)
              if rec.kind == "open_loop" else list(range(want)))
    cols = {i: arrivals.check_columns(cfg["t"], cell.traffic["check_columns"],
                                      seed, i) for i in sample}
    xpool, ypool = pools(rec, seed)
    return compare(cfg, cell.limits, seed, reqs, {}, cols, xpool, ypool,
                   control=precision)


def tiny(cell):
    """The cell at a size the CPU serves in a test: two wave shapes, a
    small pool, a few dozen requests."""
    cfg, tr = dict(cell.config, p=64, t=128), dict(cell.traffic)
    cfg["serving"] = dict(cfg["serving"], wave_buckets=[16, 64],
                          max_pending_rows=4096)
    tr.update(pool_rows=256, check_columns=24)
    if tr["kind"] == "open_loop":
        tr.update(rows=[8, 64], rate_rows_per_s=800, check_requests=1000)
    else:
        tr.update(rows=[24, 49], max_requests=48, check_requests=48)
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def faults(kind: str) -> dict:
    """Planted faults in the wave program: the Pearson sums come back
    unchanged, half of the wave's rows are left out, or a prediction is
    altered.  The open-loop mix is unscored, so it keeps no state across
    waves and cannot have the first."""
    from repro.serving_encoders.service import EncoderService

    init = EncoderService.__init__

    def wrap(fault):
        def patched(self, *a, **k):
            init(self, *a, **k)
            self._predict_mixed = (lambda f: lambda *args: fault(f, *args))(
                self._predict_mixed)
        return patched

    def unchanged(f, X, Yt, onehot, sums_in, *rest):
        P, _ = f(X, Yt, onehot, sums_in, *rest)
        return P, sums_in

    def half_batch(f, X, Yt, onehot, sums_in, *rest):
        m = X.shape[0] // 2
        return f(X.at[m:].set(0), Yt, onehot.at[m:].set(0), sums_in, *rest)

    def altered(f, *args):
        P, sums = f(*args)
        return P.at[0].multiply(1.01), sums

    planted = [("half_batch", half_batch), ("answer_altered", altered)]
    if kind != "open_loop":
        planted.insert(0, ("state_unchanged", unchanged))
    return {name: (EncoderService, "__init__", wrap(fn))
            for name, fn in planted}


def schedule(rec, seed: int, seconds: float) -> list[arrivals.Request]:
    if rec.kind == "open_loop":
        return arrivals.open_loop(rec.traffic, rec.config, seed, seconds)
    return arrivals.closed_loop(rec.traffic, rec.config, seed,
                                rec.traffic["max_requests"])


def pools(rec, seed: int):
    xpool = gen.feature_pool(seed, rec.traffic["pool_rows"], rec.config)
    ypool = (gen.target_pool(seed, rec.traffic["pool_rows"], rec.config)
             if rec.traffic["scored_share"] > 0 else None)
    return xpool, ypool


def run(h: Harness, seed: int, seconds: float, budget: int) -> None:
    rec = h.rec
    config = rec.config
    reqs = schedule(rec, seed, seconds)
    t0 = time.perf_counter()
    path = write_bundle(h, seed, config)
    t1 = time.perf_counter()
    xpool, ypool = pools(rec, seed)
    fleet = Fleet(config, path)
    warm_up(fleet, config, xpool, ypool, rec.traffic["scored_share"] > 0)
    log(f"bench: set-up: bundle {t1 - t0:.1f} s, pools, loads and warm-up "
        f"{time.perf_counter() - t1:.1f} s")

    want = rec.traffic["check_requests"]
    # Open loop: a sample of the schedule.  Closed loop: how many requests
    # the window reaches is not known before, so its first ones (in an
    # order the seed permuted) are compared.
    sample = (arrivals.check_sample([r.rows for r in reqs], want, seed)
              if rec.kind == "open_loop" else list(range(min(want, len(reqs)))))
    recorder = Recorder(sample, config, seed, rec)
    drive = open_loop if rec.kind == "open_loop" else closed_loop
    with h.window():
        drive(h, fleet, reqs, xpool, ypool, recorder, seconds)
    rec.attempted = len(rec.requests)
    rec.memory_peak_bytes = h.memory_peak()
    served = {q["index"] for q in rec.requests}
    cols = {i: c for i, c in recorder.cols.items() if i in served}
    del fleet
    gc.collect()
    t0 = time.perf_counter()
    rec.checks = compare(config, rec.limits, seed, reqs, recorder.answers,
                         cols, xpool, ypool)
    log(f"bench: reference check {time.perf_counter() - t0:.1f} s "
        f"({len(cols)} requests)")
