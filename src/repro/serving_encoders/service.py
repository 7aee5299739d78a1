"""EncoderService — mixed-wave prediction serving over a registry.

The LLM side of this repo serves decode traffic in fixed-shape *waves*
(``serving.engine.ServeEngine``: pad/stack → one compiled program reused
across waves).  This module is the same deployment pattern adapted to
encoding and hardened for the multi-tenant fleet: concurrent
``PredictRequest``\\ s are micro-batched per model, their rows concatenated
and cut into fixed-shape waves (the ragged tail zero-padded), and every
wave runs ONE compiled program per wave shape.  Fixed shapes mean one
compilation per distinct wave shape, reused forever after: the
``compile_count`` attribute counts actual traces and the fleet CI lane
asserts it equals the number of wave buckets actually flown.

**Mixed waves** (the fleet front-end).  Scored and unscored requests —
from any number of tenants — pack into the SAME waves.  The compiled
program (``_predict_mixed``) takes, next to the padded feature block, a
per-row request one-hot (``(wave_rows, score_slots)``; the
``foldstats._FixedShapeUpdate`` masking pattern) and a per-slot Pearson
sum carry, and emits the wave's predictions plus the updated ``(s, 5, t)``
running sums — so one program serves the whole traffic mix and the old
private-wave path for scored requests is retired.

Two exactness properties make the packed serve BIT-identical to serving
each request alone (the replay harness gates this):

* **Row independence.**  Each prediction row is ``x @ W`` standardized /
  de-standardized elementwise — the compiled program is keyed only by the
  wave shape, and a row's output never depends on what the other rows
  hold, so packing requests together (or padding with zeros) cannot
  change any row's bits.
* **Sequential sum chaining.**  The per-slot Pearson sums are reduced by
  a sequential scan over the wave's rows, seeded with the slot's carry
  from the request's previous wave.  A row whose one-hot weight is zero
  contributes an exact ``±0`` — and adding ``±0`` to a float accumulator
  is exact — so a request's final sums are the SAME sequential f32 chain
  over its own rows whether they sit at wave offset 0 (served alone) or
  anywhere inside a shared wave, for every wave-bucket ladder and cut.
  (A lane-parallel ``jnp.sum`` would regroup the chain by absolute row
  position and break this.)

**Staging.**  Each wave is built on the host in a set of buffers the
service keeps (``_StagingPool``: two sets per wave shape, taken in turn)
rather than in fresh arrays, which at whole-brain widths cost a page fault
per page on every wave.  The build writes every byte of the set — the
requests' rows, and zeros over the pad rows, the unscored rows' targets,
the one-hot and the unclaimed sum slots — so the program receives exactly
the bits fresh zero arrays would give, and both properties above hold.
A set is refilled only once the wave last built in it has finished: the
transfer in may still read the buffer after it returns (on the CPU the
device array IS the buffer).

Wave shapes come from ``wave_buckets`` (2–3 ladder sizes, each compiled
once, picked per wave by the rows remaining — mixed small/large traffic
stops paying the big shape's pad fraction) or the single ``wave_rows``;
``ServiceStats`` records pad economics per bucket AND per tenant
(rows/bytes/requests/errors — the fleet's accounting unit).

**Graceful degradation.**  A model whose bundle fails to load or
materialise mid-serve (truncated shard, flipped manifest bytes, eviction
race) degrades ONLY its own requests: the typed ``BundleError`` /
``RegistryError`` is surfaced on each affected ``PredictResult.error``,
the bundle is evicted, and the batch's other tenants are served normally.
Malformed *requests* still refuse the whole batch up front (pass 1), so a
bad client cannot waste another tenant's completed device work.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Sequence

import numpy as np

from repro import obs
from repro.serving_encoders.bundle import BundleError
from repro.serving_encoders.registry import EncoderRegistry, RegistryError


# Full f32 products in the wave programs: at the TPU's default f32 matmul
# precision, served predictions at p = 16,384 came back from a v5e chip
# with 3.5e-3 relative error against float64 (tolerance 1e-3).
_PRECISION = "highest"

class ServiceError(ValueError):
    """Malformed request: unknown model is handled by the registry; this is
    for empty/shape-mismatched feature blocks and admission rejections."""


@dataclasses.dataclass
class PredictRequest:
    """One client request: raw (un-standardized) stimulus features for one
    model, optionally with measured targets to score against.  ``tenant``
    is the accounting principal (defaults to the model name)."""

    model: str
    features: np.ndarray                 # (rows, p) raw features
    targets: np.ndarray | None = None    # (rows, t) → score with Pearson r
    tenant: str | None = None

    @property
    def tenant_id(self) -> str:
        return self.tenant if self.tenant is not None else self.model


@dataclasses.dataclass
class PredictResult:
    model: str
    predictions: np.ndarray | None       # (rows, t) raw-unit predictions
    pearson_r: np.ndarray | None = None  # (t,) when targets were given
    # Typed load/serve fault (BundleError/RegistryError) that degraded
    # this request — the fleet's per-tenant failure unit.  None = served.
    error: Exception | None = None


@dataclasses.dataclass
class ServiceStats:
    waves: int = 0
    rows: int = 0                        # real (unpadded) rows served
    pad_rows: int = 0                    # zero rows added to fill waves
    requests: int = 0
    # Per wave shape actually flown: {wave_rows: {"waves", "rows",
    # "pad_rows"}} — the observable pad economics of bucketing.
    per_bucket: dict = dataclasses.field(default_factory=dict)
    # Per tenant: {"rows", "bytes", "requests", "scored", "errors",
    # "rejected"} — the fleet's accounting unit (bytes = feature + target
    # payload served for the tenant).
    per_tenant: dict = dataclasses.field(default_factory=dict)

    def record_wave(self, wave_rows: int, real: int) -> None:
        b = self.per_bucket.setdefault(
            wave_rows, {"waves": 0, "rows": 0, "pad_rows": 0})
        b["waves"] += 1
        b["rows"] += real
        b["pad_rows"] += wave_rows - real
        self.waves += 1
        self.pad_rows += wave_rows - real

    def tenant(self, tenant: str) -> dict:
        return self.per_tenant.setdefault(
            tenant, {"rows": 0, "bytes": 0, "requests": 0, "scored": 0,
                     "errors": 0, "rejected": 0})

    def record_request(self, tenant: str, rows: int, nbytes: int,
                       scored: bool) -> None:
        acct = self.tenant(tenant)
        acct["rows"] += rows
        acct["bytes"] += nbytes
        acct["requests"] += 1
        acct["scored"] += int(scored)

    def record_error(self, tenant: str) -> None:
        self.tenant(tenant)["errors"] += 1

    def record_rejected(self, tenant: str) -> None:
        self.tenant(tenant)["rejected"] += 1

    def to_dict(self) -> dict:
        """Shared ``repro.obs`` stats schema (kind ``"service"``) — the
        shape ``launch/serve.py``, the benches, and the fleet workers
        report, mergeable across processes by summing the flat fields."""
        return {
            "schema": obs.SCHEMA_VERSION,
            "kind": "service",
            "waves": int(self.waves),
            "rows": int(self.rows),
            "pad_rows": int(self.pad_rows),
            "requests": int(self.requests),
            "per_bucket": {int(k): dict(v)
                           for k, v in sorted(self.per_bucket.items())},
            "per_tenant": {k: dict(v)
                           for k, v in sorted(self.per_tenant.items())},
        }


# -- mixed-wave packing ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WaveSegment:
    """Rows ``[req_lo, req_hi)`` of request ``req`` land at wave offset
    ``wave_lo``; ``slot`` is the request's Pearson score slot within this
    wave (None = unscored)."""

    req: int
    req_lo: int
    req_hi: int
    wave_lo: int
    slot: int | None


@dataclasses.dataclass(frozen=True)
class MixedWave:
    rows: int                    # wave shape flown (a bucket size)
    fill: int                    # real rows (rows - fill are padding)
    segments: tuple              # WaveSegment, contiguous from offset 0


def plan_mixed_waves(req_rows: Sequence[int], scored: Sequence[bool],
                     next_wave: Callable[[int], int],
                     score_slots: int) -> list[MixedWave]:
    """Pack ragged scored/unscored requests into fixed-shape mixed waves.

    Requests flow into waves in arrival order; ``next_wave(remaining)``
    picks each wave's shape (the bucket-ladder policy).  Every scored
    request intersecting a wave holds one of the wave's ``score_slots``
    one-hot slots; when a NEW scored request would need a slot and none is
    free the wave closes early (its tail rows become padding) — the slot
    count is static so the compiled program's shape never changes.

    Pure and deterministic: the property harness replays plans against a
    per-request reference serve, and the fleet bench replays the same
    traffic trace through the same packer.
    """
    if score_slots < 1:
        raise ServiceError(f"score_slots must be >= 1, got {score_slots}")
    waves: list[MixedWave] = []
    remaining = sum(req_rows)
    r, done = 0, 0                      # cursor: request index, rows consumed
    while remaining:
        w = next_wave(remaining)
        fill, slots = 0, 0
        segs: list[WaveSegment] = []
        while fill < w and r < len(req_rows):
            rows = req_rows[r]
            if done == rows:                       # exhausted → advance
                r, done = r + 1, 0
                continue
            slot = None
            if scored[r]:
                if slots == score_slots:
                    break                          # close the wave early
                slot, slots = slots, slots + 1
            take = min(w - fill, rows - done)
            segs.append(WaveSegment(r, done, done + take, fill, slot))
            fill += take
            done += take
            remaining -= take
            if done == rows:
                r, done = r + 1, 0
        waves.append(MixedWave(rows=w, fill=fill, segments=tuple(segs)))
    return waves


class _StagingSet:
    """One wave's host inputs ``(X, Yt, onehot, sums_in)`` and ``fence``,
    the device arrays of the last wave built in them (None: none pending);
    ``busy`` while a builder holds it."""

    __slots__ = ("arrays", "fence", "busy")

    def __init__(self, rows: int, p: int, t: int, slots: int):
        # Empty, not zeros: the first build writes every byte anyway.
        self.arrays = (np.empty((rows, p), np.float32),
                       np.empty((rows, t), np.float32),
                       np.empty((rows, slots), np.float32),
                       np.empty((slots, 5, t), np.float32))
        self.fence = None
        self.busy = True

    def wait(self) -> None:
        """Block until the wave last built here no longer needs it."""
        import jax

        if self.fence is not None and not all(
                a.is_ready() for a in self.fence):
            jax.block_until_ready(self.fence)
        self.fence = None


class _StagingPool:
    """The host buffers waves are built in: per key ``(wave rows, p, t,
    slots)`` two sets, taken in turn so one can be filled while the wave
    built in the other is in flight; beyond ``max_keys`` keys the least
    recently used is dropped.  A set is held by one builder at a time; a
    builder that finds both held (concurrent ``serve`` calls) gets a set
    of its own that is not kept."""

    def __init__(self, max_keys: int):
        self.max_keys = max_keys
        self._lock = threading.Lock()
        self._keys: collections.OrderedDict = collections.OrderedDict()

    def __len__(self) -> int:
        return len(self._keys)

    def take(self, key: tuple) -> tuple[_StagingSet, bool]:
        """A set for ``key``, held until :meth:`release`; and whether it
        was reused (else allocated now)."""
        with self._lock:
            entry = self._keys.pop(key, None) or {"sets": [], "turn": 0}
            self._keys[key] = entry
            while len(self._keys) > self.max_keys:
                self._keys.popitem(last=False)
            for _ in range(2):
                i = entry["turn"]
                entry["turn"] ^= 1
                if i == len(entry["sets"]):
                    entry["sets"].append(_StagingSet(*key))
                    return entry["sets"][i], False
                s = entry["sets"][i]
                if not s.busy:
                    s.busy = True
                    return s, True
        return _StagingSet(*key), False

    def release(self, staging: _StagingSet, fence) -> None:
        with self._lock:
            staging.fence = fence
            staging.busy = False


def _stage(arrays: tuple, wave: MixedWave, blocks: list, targets: list,
           req_sums: dict) -> tuple[int, bool]:
    """Write ``wave``'s inputs over whatever ``arrays`` held: the segments'
    rows, targets, one-hot and carried sums, and zeros everywhere else.
    Returns the target rows zeroed (pad plus unscored rows) and whether
    any segment is scored."""
    X, Yt, onehot, sums_in = arrays
    onehot.fill(0.0)
    claimed = np.zeros(sums_in.shape[0], bool)
    zeroed = wave.rows - wave.fill
    for seg in wave.segments:
        dst = slice(seg.wave_lo, seg.wave_lo + seg.req_hi - seg.req_lo)
        X[dst] = blocks[seg.req][seg.req_lo:seg.req_hi]
        if seg.slot is None:
            Yt[dst] = 0.0
            zeroed += seg.req_hi - seg.req_lo
        else:
            Yt[dst] = targets[seg.req][seg.req_lo:seg.req_hi]
            onehot[dst, seg.slot] = 1.0
            sums_in[seg.slot] = req_sums[seg.req]
            claimed[seg.slot] = True
    X[wave.fill:] = 0.0
    Yt[wave.fill:] = 0.0
    sums_in[~claimed] = 0.0
    return zeroed, bool(claimed.any())


class EncoderService:
    """Micro-batching mixed-wave server over an ``EncoderRegistry``.

    >>> service = EncoderService(registry, wave_buckets=(32, 128))
    >>> results = service.serve([PredictRequest("sub-01", X1),
    ...                          PredictRequest("sub-02", X2, targets=Y2)])

    Requests for the same model are packed together — scored and unscored
    alike — so many small concurrent requests cost the same compiled
    program as one large one.  Wave shapes come from ``wave_buckets`` when
    given (each compiled once, picked per wave by the rows remaining) or
    the single ``wave_rows`` otherwise; ``serve(..., wave_rows=...)`` pins
    one shape per call.  Every distinct (program, wave shape) pair
    compiles exactly once per service lifetime — ``compile_count`` counts
    actual traces, and mixing scored/unscored traffic never adds one.

    ``score_slots`` bounds how many scored requests share one wave (the
    static one-hot width); ``prefetch_next=True`` touches the registry for
    the NEXT queued model on a background thread while the current model's
    waves are in flight (hot-bundle prefetch — needs the registry's lock,
    which ``EncoderRegistry`` always holds across mutations).
    """

    def __init__(self, registry: EncoderRegistry, *, wave_rows: int = 128,
                 wave_buckets: Sequence[int] | None = None,
                 score_slots: int = 4, prefetch_next: bool = False,
                 return_predictions: bool = True):
        import jax
        import jax.numpy as jnp

        self.registry = registry
        if wave_rows < 1:
            raise ServiceError(f"wave_rows must be >= 1, got {wave_rows}")
        self.wave_rows = wave_rows
        if wave_buckets is not None:
            wave_buckets = tuple(sorted({int(b) for b in wave_buckets}))
            if not wave_buckets or wave_buckets[0] < 1:
                raise ServiceError(f"wave_buckets must be positive ints, "
                                   f"got {wave_buckets}")
        self.wave_buckets = wave_buckets
        if score_slots < 1:
            raise ServiceError(f"score_slots must be >= 1, "
                               f"got {score_slots}")
        self.score_slots = score_slots
        self.prefetch_next = prefetch_next
        self.return_predictions = return_predictions
        self.compiles = obs.CompileCounter("service.predict")
        self._seen_shapes: set = set()
        self._waves_flown = 0        # numbers each mixed wave's spans
        self.stats = ServiceStats()
        # Two keys a bucket: one (p, t) shape at a time per bucket, with
        # room for a second while the traffic moves between two.
        self._staging = _StagingPool(
            2 * len(wave_buckets) if wave_buckets else 2)

        def _predict(X, W, mu_x, sd_x, mu_y, sd_y):
            # Python side effect at TRACE time: runs once per distinct
            # (wave shape, weight shape/dtype/sharding) signature — the
            # compile counter the serving bench/CI lane asserts on.
            self.compiles.mark()
            with jax.named_scope("wave_matmul"):
                Xs = (X - mu_x) / sd_x
                P = jnp.matmul(Xs, W, precision=_PRECISION,
                               preferred_element_type=jnp.float32)
                return P * sd_y + mu_y

        def _predict_mixed(X, Yt, onehot, sums_in, W, mu_x, sd_x,
                           mu_y, sd_y):
            # THE fleet program: predictions for the whole mixed wave plus
            # the per-slot Pearson running sums, chained through sums_in.
            # The reduction over rows is a SEQUENTIAL scan (unrolled in
            # blocks of 8, still one chain): zero-weight rows add exact
            # ±0, so a request's sums are bit-identical at any wave
            # offset/cut to serving it alone — the replay-harness gate.
            self.compiles.mark()
            with jax.named_scope("wave_matmul"):
                Xs = (X - mu_x) / sd_x
                P = jnp.matmul(Xs, W, precision=_PRECISION,
                               preferred_element_type=jnp.float32)
                P = P * sd_y + mu_y
            m = X.shape[0]
            m8 = -(-m // 8) * 8
            pad = ((0, m8 - m), (0, 0))

            def step(sums, blk):
                y8, p8, w8 = blk                    # (8, t) (8, t) (8, s)
                for i in range(8):                  # sequential, in order
                    y, q, w = y8[i], p8[i], w8[i]
                    terms = jnp.stack([y, q, y * y, q * q, y * q])
                    sums = sums + w[:, None, None] * terms[None]
                return sums, None

            with jax.named_scope("wave_scan"):
                Yp = jnp.pad(Yt, pad)
                Pp = jnp.pad(P, pad)
                wp = jnp.pad(onehot, pad)           # pad rows: weight 0
                sums_out, _ = jax.lax.scan(
                    step, sums_in,
                    (Yp.reshape(m8 // 8, 8, -1), Pp.reshape(m8 // 8, 8, -1),
                     wp.reshape(m8 // 8, 8, -1)))
            return P, sums_out

        self._predict = jax.jit(_predict)
        self._predict_mixed = jax.jit(_predict_mixed)

    @property
    def compile_count(self) -> int:
        """Total traces of the two serve programs (thin alias over the
        shared :class:`repro.obs.CompileCounter`)."""
        return self.compiles.count

    def _expect_shape(self, key: tuple):
        """Strict-sentinel window for one wave flight: a shape key seen
        before must trace 0 new programs; a fresh key is allowed exactly
        one.  Under ``REPRO_OBS_STRICT=1`` a violation raises at trace
        time (``obs.RecompileError``) instead of skewing the counter."""
        fresh = key not in self._seen_shapes
        self._seen_shapes.add(key)
        return self.compiles.expect(at_most=1 if fresh else 0)

    # -- wave planning -------------------------------------------------------
    def _plan_waves(self, n_rows: int, wave_rows: int | None) -> list[int]:
        """Wave shapes covering ``n_rows``: the pinned single shape, or a
        bucket-ladder plan — the largest bucket while full waves remain,
        then the min-pad cover of the tail (a single bucket that swallows
        it, or the greedy descending ladder when that pads less — e.g. a
        33-row tail on (32, 128) flies 32+32, pad 31, not 128, pad 95);
        equal pad prefers the single wave (fewer dispatches)."""
        if wave_rows is not None or self.wave_buckets is None:
            w = wave_rows if wave_rows is not None else self.wave_rows
            return [w] * -(-n_rows // w)
        big = self.wave_buckets[-1]
        sizes = [big] * (n_rows // big)
        tail = n_rows - big * len(sizes)
        if not tail:
            return sizes
        single = [next(b for b in self.wave_buckets if b >= tail)]
        ladder, rem = [], tail
        for b in reversed(self.wave_buckets):
            take = rem // b
            ladder += [b] * take
            rem -= b * take
        if rem:
            ladder.append(self.wave_buckets[0])
        return sizes + (ladder if sum(ladder) < single[0] else single)

    def _next_wave(self, remaining: int, wave_rows: int | None) -> int:
        """First wave of the ladder plan for ``remaining`` rows — the
        incremental form the mixed packer re-plans with after an early
        (slot-exhausted) wave close."""
        return self._plan_waves(remaining, wave_rows)[0]

    def _pad(self, block: np.ndarray, rows: int) -> np.ndarray:
        pad = rows - block.shape[0]
        if not pad:
            return block
        return np.concatenate(
            [block, np.zeros((pad, block.shape[1]), np.float32)])

    # -- windowed serving (whole-brain bundles) ------------------------------
    def predict_columns(self, model: str, features: np.ndarray,
                        col_range: tuple[int, int], *,
                        wave_rows: int | None = None) -> np.ndarray:
        """Predict ONE target-column window of one model.

        The whole-brain serving path: the registry pages in (and charges)
        only the weight column shards overlapping ``col_range`` — a
        request for 2k voxels of a 262k-voxel bundle faults in one mmap'd
        shard, not the ``p·t`` matrix.  Rows fly in the same fixed-shape
        waves as ``serve`` and each (wave shape, shard width) pair
        compiles once, reused across shards, waves, and calls.

        Returns the ``(rows, hi - lo)`` raw-unit predictions.
        """
        import jax.numpy as jnp

        lo, hi = col_range
        bundle = self.registry.bundle(model)
        p, t = bundle.shape
        if not (0 <= lo < hi <= t):
            raise ServiceError(f"column window [{lo}, {hi}) invalid for "
                               f"{model!r} with t={t}")
        feats = np.asarray(features, np.float32)
        if feats.ndim != 2 or feats.shape[1] != p or not feats.size:
            raise ServiceError(f"request for {model!r}: features "
                               f"{feats.shape} incompatible with p={p}")
        if wave_rows is not None and wave_rows < 1:
            raise ServiceError(f"wave_rows must be >= 1, got {wave_rows}")
        max_wave = wave_rows if wave_rows is not None else (
            self.wave_buckets[-1] if self.wave_buckets else self.wave_rows)
        shards = self.registry.get_columns(model, (lo, hi),
                                           wave_rows=max_wave)
        first_lo = shards[0].bounds[0]
        # Enqueue all (wave × shard) programs before any host pull —
        # async dispatch overlaps them with the padding of later waves.
        parts, counts = [], []
        pos = 0
        for w in self._plan_waves(feats.shape[0], wave_rows):
            with obs.span("serve.wave.build", rows=w, model=model):
                chunk = jnp.asarray(self._pad(feats[pos:pos + w], w))
            real = min(w, feats.shape[0] - pos)
            outs = []
            with obs.span("serve.wave.execute", rows=w,
                          shards=len(shards)):
                for e in shards:
                    with self._expect_shape(
                            ("predict", w, p, int(e.W.shape[1]))):
                        outs.append(self._predict(chunk, e.W, e.mu_x,
                                                  e.sd_x, e.mu_y, e.sd_y))
            parts.append(outs)
            counts.append(real)
            self.stats.record_wave(w, real)
            pos += w
        host = []
        for outs, c in zip(parts, counts):
            row = (np.concatenate([np.asarray(o) for o in outs], axis=1)
                   if len(outs) > 1 else np.asarray(outs[0]))
            host.append(row[:c])
        out = np.concatenate(host) if len(host) > 1 else host[0]
        self.stats.rows += feats.shape[0]
        self.stats.requests += 1
        return out[:, lo - first_lo:hi - first_lo]

    # -- serving -------------------------------------------------------------
    def _serve_group(self, model: str, reqs: list[PredictRequest],
                     blocks: list[np.ndarray], t: int, max_wave: int,
                     wave_rows: int | None) -> list[PredictResult]:
        """Fly one model's packed mixed waves; results in ``reqs`` order."""
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        entry = self.registry.get(model, wave_rows=max_wave,
                                  score_slots=self.score_slots)
        enc_args = (entry.weights, entry.mu_x, entry.sd_x,
                    entry.mu_y, entry.sd_y)
        s = self.score_slots
        scored = [r.targets is not None for r in reqs]
        targets = [None if r.targets is None
                   else np.asarray(r.targets, np.float32) for r in reqs]
        plan = plan_mixed_waves(
            [b.shape[0] for b in blocks], scored,
            lambda rem: self._next_wave(rem, wave_rows), s)

        # Per-request running Pearson sums — the f32 chain the compiled
        # scan continues from wave to wave (exact, see module docstring).
        req_sums = {j: np.zeros((5, t), np.float32)
                    for j, sc in enumerate(scored) if sc}
        p = blocks[0].shape[1]
        traced = obs.current() is not None
        flown: list[tuple[MixedWave, object, int]] = []
        for wave in plan:
            seq = self._waves_flown
            self._waves_flown += 1
            staging, reused = self._staging.take((wave.rows, p, t, s))
            obs.get_metrics().counter(
                "wave_staging",
                outcome="reused" if reused else "allocated").inc()
            # What must finish before the set is refilled: the transfer
            # until the program is enqueued, then the program.
            fence = None
            try:
                with obs.span("serve.wave.build", rows=wave.rows,
                              fill=wave.fill, model=model, wave=seq,
                              reused=int(reused)) as span:
                    staging.wait()
                    zeroed, has_scored = _stage(
                        staging.arrays, wave, blocks, targets, req_sums)
                    span.set(zeroed_rows=zeroed)
                # Under a tracer each span below waits for its device work,
                # so it times the copy or the program rather than its
                # enqueue.
                nbytes = sum(a.nbytes for a in staging.arrays)
                with obs.transfer("serve.wave.h2d", "h2d_bytes", nbytes,
                                  site="wave", rows=wave.rows,
                                  fill=wave.fill, wave=seq):
                    inputs = fence = tuple(
                        jnp.asarray(a) for a in staging.arrays)
                    if traced:
                        jax.block_until_ready(inputs)
                with obs.span("serve.wave.execute", rows=wave.rows,
                              fill=wave.fill, model=model, wave=seq):
                    with self._expect_shape(("mixed", wave.rows, p, t, s)):
                        P, sums_out = fence = self._predict_mixed(
                            *inputs, *enc_args)
                    self.stats.record_wave(wave.rows, wave.fill)
            finally:
                self._staging.release(staging, fence)
            if has_scored:
                # The chain is a data dependency: the slot carries must
                # land on host before the request's NEXT wave is built.
                # Unscored waves stay async-enqueued, two a wave shape
                # (its staging sets) in flight at once.
                with obs.span("serve.wave.sums_wait", rows=wave.rows,
                              wave=seq):
                    host_sums = np.asarray(sums_out)
                for seg in wave.segments:
                    if seg.slot is not None:
                        req_sums[seg.req] = host_sums[seg.slot]
            flown.append((wave, P, seq))

        out_pred = None
        if self.return_predictions:
            out_pred = {j: np.empty((b.shape[0], t), np.float32)
                        for j, b in enumerate(blocks)}
            for wave, P, seq in flown:
                with obs.transfer("serve.copyback", "d2h_bytes", P.nbytes,
                                  site="wave", rows=wave.rows, wave=seq):
                    host = np.asarray(P)
                    for seg in wave.segments:
                        out_pred[seg.req][seg.req_lo:seg.req_hi] = host[
                            seg.wave_lo:seg.wave_lo + seg.req_hi - seg.req_lo]

        with obs.span("serve.results", requests=len(reqs)):
            results = []
            for j, req in enumerate(reqs):
                r = None
                if scored[j]:
                    # Finalise from the accumulated chain with the kernel's
                    # formula: identical sums (packed vs alone) → same r.
                    r = np.asarray(ops.pearson_r_from_sums(
                        req_sums[j].astype(np.float64), blocks[j].shape[0]))
                results.append(PredictResult(
                    model=model,
                    predictions=None if out_pred is None else out_pred[j],
                    pearson_r=r))
                self.stats.rows += blocks[j].shape[0]
                self.stats.record_request(
                    req.tenant_id, blocks[j].shape[0],
                    blocks[j].nbytes
                    + (targets[j].nbytes if scored[j] else 0), scored[j])
        return results

    def _prefetch(self, model: str, max_wave: int) -> None:
        """Hot-bundle prefetch: touch the registry for the next queued
        model while the current model's waves are in flight.  Faults stay
        silent here — they surface (typed, per request) when the model is
        actually served."""
        try:
            self.registry.get(model, wave_rows=max_wave,
                              score_slots=self.score_slots)
        except Exception:
            pass

    def serve(self, requests: Sequence[PredictRequest], *,
              wave_rows: int | None = None) -> list[PredictResult]:
        if wave_rows is not None and wave_rows < 1:
            raise ServiceError(f"wave_rows must be >= 1, got {wave_rows}")
        # The largest shape this call may fly — what the residency account
        # must be charged at.
        max_wave = wave_rows if wave_rows is not None else (
            self.wave_buckets[-1] if self.wave_buckets else self.wave_rows)
        # Micro-batch: group request indices per model, preserving arrival
        # order within each model's queue.
        groups: dict[str, list[int]] = {}
        for i, req in enumerate(requests):
            groups.setdefault(req.model, []).append(i)

        # Pass 1 — validate EVERY request (features and targets) against
        # its bundle's MANIFEST before any device work, so one malformed
        # request cannot discard another model's completed predictions.
        # Manifest-only access keeps nothing resident: a batch spanning
        # more models than the registry budget fits must not pin them all
        # at once, so loading waits for pass 2 (one model at a time).
        prepared: dict[str, list] = {}
        for model, idxs in groups.items():
            p, t = self.registry.bundle(model).shape
            # A model whose bundle could never fit the budget at this wave
            # size dooms the batch — refuse before ANY model's compute.
            self.registry.ensure_servable(model, wave_rows=max_wave,
                                          score_slots=self.score_slots)
            blocks = []
            for i in idxs:
                feats = np.asarray(requests[i].features, np.float32)
                if feats.ndim != 2 or feats.shape[1] != p or not feats.size:
                    raise ServiceError(
                        f"request for {model!r}: features {feats.shape} "
                        f"incompatible with the bundle's p={p}")
                if requests[i].targets is not None and \
                        np.shape(requests[i].targets) != (feats.shape[0], t):
                    raise ServiceError(
                        f"request for {model!r}: targets "
                        f"{np.shape(requests[i].targets)} != expected "
                        f"({feats.shape[0]}, {t})")
                blocks.append(feats)
            prepared[model] = blocks

        # Pass 2 — load (LRU touch, residency charged at the largest wave
        # actually flown), pack, and fly each model's mixed waves.  A
        # load/serve fault degrades ONLY that model's requests.
        results: list[PredictResult | None] = [None] * len(requests)
        order = list(groups)
        pending: threading.Thread | None = None
        for gi, model in enumerate(order):
            if pending is not None:
                pending.join()                     # prefetched THIS model
                pending = None
            if self.prefetch_next and gi + 1 < len(order):
                pending = threading.Thread(
                    target=self._prefetch, args=(order[gi + 1], max_wave),
                    daemon=True)
                pending.start()
            idxs = groups[model]
            t = self.registry.bundle(model).shape[1]
            try:
                group_results = self._serve_group(
                    model, [requests[i] for i in idxs], prepared[model],
                    t, max_wave, wave_rows)
            except (BundleError, RegistryError) as err:
                # Graceful degradation: evict the faulty bundle, surface
                # the typed error on each of the model's requests, keep
                # serving the other tenants.
                self.registry.evict(model)
                group_results = []
                for i in idxs:
                    self.stats.record_error(requests[i].tenant_id)
                    group_results.append(PredictResult(
                        model=model, predictions=None, error=err))
            for i, res in zip(idxs, group_results):
                results[i] = res
            self.stats.requests += len(idxs)
        if pending is not None:
            pending.join()
        return results                                 # arrival order


def reference_serve(service: EncoderService,
                    requests: Sequence[PredictRequest], *,
                    wave_rows: int | None = None) -> list[PredictResult]:
    """The per-request reference: each request served ALONE (no packing,
    no wave sharing).  The replay harness and the property tests gate the
    packed mixed-wave serve bit-identical against this."""
    return [service.serve([req], wave_rows=wave_rows)[0]
            for req in requests]


__all__ = ["EncoderService", "MixedWave", "PredictRequest", "PredictResult",
           "ServiceError", "ServiceStats", "WaveSegment", "plan_mixed_waves",
           "reference_serve"]
