"""The one traffic generator: reads a traffic mix's parameters and makes
its requests from the seed.

Request sizes and model popularity follow
``repro.serving_encoders.traffic`` (``ragged_requests``: rows uniform in
``[lo, hi)``; ``make_mixed_trace``: Zipf popularity ``1/(rank+1)^a``),
copied here so the yardstick does not move with the program.

Every seed gets the same work: the row counts, the inter-arrival gaps and
the subjects are fixed multisets (stratified quantiles of their
distributions), and the seed only orders them and picks where in the row
pools each request's rows are cut.  Two seeds then differ in order, not
in the amount of work, so their runs spread as little as two runs of one
seed.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # offset from the window's start (open loop)
    subject: int
    rows: int
    offset: int           # first row in the feature (and target) pool
    scored: bool
    client: int = -1      # closed loop: the client that sends it


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def stratified_rows(n: int, lo: int, hi: int) -> np.ndarray:
    """n row counts spread evenly over ``[lo, hi)``."""
    q = (np.arange(n) + 0.5) / n
    return (lo + np.floor(q * (hi - lo))).astype(np.int64)


def zipf_weights(n_models: int, a: float) -> np.ndarray:
    w = 1.0 / (np.arange(n_models) + 1.0) ** a
    return w / w.sum()


def stratified_subjects(n: int, weights: np.ndarray) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return np.searchsorted(np.cumsum(weights), q).astype(np.int64)


def open_loop(traffic: dict, config: dict, seed: int,
              seconds: float) -> list[Request]:
    """Poisson-like arrivals at ``rate_rows_per_s`` for ``seconds``.

    The gaps are the quantiles of an exponential distribution with the
    mean that gives the rate; gaps, row counts and subjects are shuffled
    once, into one fixed sample path of arrivals.  The seed rotates that
    path (the window starts at a point of it drawn from the seed) and
    picks the pool rows each request carries.  A 95th percentile of a
    hundred-odd requests is set by the path's largest bursts; with a path
    of its own per seed it swung by half its value from seed to seed, and
    rotated it keeps them.
    """
    lo, hi = traffic["rows"]
    mean_rows = (lo + hi - 1) / 2
    req_rate = traffic["rate_rows_per_s"] / mean_rows
    n = max(1, int(round(req_rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / req_rate
    gaps *= seconds / gaps.sum()                # the gaps fill the window
    path = np.random.default_rng(0)             # the one fixed path
    gaps = path.permutation(gaps)
    rows = path.permutation(stratified_rows(n, lo, hi))
    w = zipf_weights(config["subjects"], traffic["zipf_a"])
    subj = path.permutation(stratified_subjects(n, w))
    turn = np.roll(np.arange(n), -int(_rng(seed, 0).integers(n)))
    gaps, rows, subj = gaps[turn], rows[turn], subj[turn]
    due = np.cumsum(gaps) - gaps[0]             # the first is due at 0
    offs = _rng(seed, 3).integers(0, traffic["pool_rows"] - rows + 1)
    scored = _rng(seed, 4).random(n) < traffic["scored_share"]
    return [Request(i, float(due[i]), int(subj[i]), int(rows[i]),
                    int(offs[i]), bool(scored[i])) for i in range(n)]


def closed_loop(traffic: dict, config: dict, seed: int,
                count: int) -> list[Request]:
    """``count`` requests for ``clients`` closed-loop clients: client c
    sends requests c, c + clients, ...; subjects round-robin per client."""
    lo, hi = traffic["rows"]
    clients = traffic["clients"]
    rows = _rng(seed, 1).permutation(stratified_rows(count, lo, hi))
    offs = _rng(seed, 3).integers(0, traffic["pool_rows"] - rows + 1)
    scored = _rng(seed, 4).random(count) < traffic["scored_share"]
    n_subj = config["subjects"]
    return [Request(i, 0.0, (i // clients + i % clients) % n_subj,
                    int(rows[i]), int(offs[i]), bool(scored[i]),
                    client=i % clients) for i in range(count)]


def check_sample(rows: list[int], want: int, seed: int) -> list[int]:
    """Indices of the requests whose answers are compared: ``want`` of
    them, drawn from the seed, always with the largest one."""
    n = len(rows)
    if n <= want:
        return list(range(n))
    pick = set(_rng(seed, 5).choice(n, size=want - 1, replace=False).tolist())
    pick.add(int(np.argmax(rows)))
    return sorted(pick)


def check_columns(t: int, want: int, seed: int, index: int) -> np.ndarray:
    """The target columns compared for one request, drawn from the seed."""
    return np.sort(_rng(seed, 6 + index).choice(t, size=min(want, t),
                                                replace=False))


def percentile(values, q: float) -> float:
    """The q-th percentile (0–100) by the nearest-rank rule."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(q / 100 * len(v)) - 1)])
