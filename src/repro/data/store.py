"""Out-of-core run store: memory-mapped fMRI runs behind a JSON manifest.

The paper's whole-brain workload (Table 1: n≈60k TRs × t≈264k targets per
subject) does not fit in device — or often host — memory, which is why its
Batch-MultiOutput design streams target batches across workers.  ``RunStore``
is the row-streaming half of that story: each acquisition *run* is written
once as a pair of ``.npy`` shards (``X``: stimulus features, ``Y``: BOLD
targets) and thereafter only ever *memory-mapped*, so ``iter_chunks`` hands
out zero-copy row batches whose resident footprint is one chunk, never
``(n, p)``.

Layout on disk::

    <root>/manifest.json          # shapes, dtypes, row offsets, fold split
    <root>/<run_id>.X.npy         # (n_run, p) feature shard
    <root>/<run_id>.Y.npy         # (n_run, t) target shard

Design points:

* **Global row order is the manifest's run order.**  Runs are concatenated
  at their recorded ``row_offset``; the k-fold split used downstream
  (``foldstats.fold_bounds`` over ``n_total``) is recorded in the manifest
  at write time so every consumer — in-memory, chunked, sharded-chunked —
  derives the identical fold assignment.
* **Read paths are read-only.**  ``open()`` maps shards with
  ``mmap_mode="r"``; writing through a served chunk raises, so a streaming
  fit can never corrupt the store it is reading.
* **Validation is eager.**  ``open()`` cross-checks every shard's header
  shape/dtype against the manifest and the run offsets against each other;
  a missing shard, a shape/dtype mismatch, or overlapping row ranges raise
  ``StoreError`` before any fit starts.
* **Chunks respect nothing but row order.**  ``iter_chunks`` slices freely
  across run boundaries (a chunk may span two runs) and across fold
  boundaries — the fold-stats accumulator splits at fold bounds itself —
  so chunk size is purely a memory/throughput knob.
"""
from __future__ import annotations

import dataclasses
import json
import os
import queue
import threading
from typing import Iterator

import numpy as np

from repro import obs
from repro.data.fmri import SubjectSpec
from repro.resilience import cleanup
from repro.resilience.policy import FaultPolicy, classify_default, retry_call

MANIFEST_NAME = "manifest.json"
_MANIFEST_VERSION = 1


class StoreError(ValueError):
    """Manifest/shard inconsistency (missing file, shape/dtype mismatch,
    overlapping or gapped row ranges)."""


def _dtype_from_name(name: str) -> np.dtype:
    """Resolve a manifest dtype name, including the ml_dtypes extras
    (``bfloat16``) that plain ``np.dtype(...)`` does not know by name."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _storage_dtype(dtype: np.dtype) -> np.dtype:
    """On-disk dtype for a logical dtype.

    ``np.save`` demotes non-native dtypes (ml_dtypes ``bfloat16``) to raw
    void records that neither numpy nor JAX will touch afterwards, so such
    shards are stored as same-width unsigned bit patterns and viewed back
    at read time — the memmap view is still zero-copy.
    """
    if dtype.kind == "V" or dtype.name == "bfloat16":
        return np.dtype(f"u{dtype.itemsize}")
    return dtype


@dataclasses.dataclass(frozen=True)
class RunEntry:
    """One acquisition run inside the store (one X/Y shard pair)."""

    run_id: str
    row_offset: int     # first global row of this run
    n_rows: int

    @property
    def row_end(self) -> int:
        return self.row_offset + self.n_rows


def _shard_paths(root: str, run_id: str) -> tuple[str, str]:
    return (os.path.join(root, f"{run_id}.X.npy"),
            os.path.join(root, f"{run_id}.Y.npy"))


def _normalize_dtype(dtype) -> np.dtype | None:
    if dtype is None:
        return None
    return _dtype_from_name(dtype) if isinstance(dtype, str) \
        else np.dtype(dtype)


@dataclasses.dataclass
class PrefetchStats:
    """Where a prefetched stream spent its waiting time.

    ``read_stall_s`` is consumer time blocked on an empty queue (the disk
    reader was the bottleneck); ``compute_stall_s`` is reader time blocked
    on a full queue (compute was the bottleneck — the overlap is working).
    A well-overlapped stream has one of the two ≈ the pipeline imbalance
    and the other ≈ 0; both ≈ 0 means the stream finished before either
    side ever waited.

    Every field is DERIVED from the prefetcher's observability spans
    (``prefetch.stage`` / ``prefetch.wait`` / ``prefetch.compute_stall``
    via ``obs.timed``) — the stats and a recorded trace are two views of
    the same measurements, never parallel bookkeeping.
    """

    chunks: int = 0
    bytes_staged: int = 0
    read_stall_s: float = 0.0
    compute_stall_s: float = 0.0

    def to_dict(self) -> dict:
        """Shared metrics-snapshot schema (``repro.obs``): flat
        snake_case fields, JSON-serialisable — what benches consume."""
        return {"schema": obs.SCHEMA_VERSION, "kind": "prefetch",
                "chunks": int(self.chunks),
                "bytes_staged": int(self.bytes_staged),
                "read_stall_s": float(self.read_stall_s),
                "compute_stall_s": float(self.compute_stall_s)}


class ChunkPrefetcher:
    """Double-buffered background reader over ``RunStore.iter_chunks``.

    A daemon thread walks the ordinary (synchronous) chunk iterator and
    *stages* each chunk — memmap page-in plus any dtype conversion — into
    one of ``depth + 2`` reusable pre-allocated host buffers (the CPU
    analogue of pinned staging memory), then hands it over through a
    bounded queue of ``depth``.  While the consumer runs the device
    accumulation on chunk *i*, the reader is already faulting in chunk
    *i+1*: the stream runs at the speed of the slower side, not their sum.

    Contracts:

    * **Bit-identical**: staging is a straight copy, so chunk order,
      shapes, and values are exactly the synchronous iterator's.
    * **Bounded residency**: ``depth + 2`` buffers of ``chunk_rows`` rows,
      allocated lazily on first iteration and released when the stream is
      exhausted or closed.  ``depth`` queued + 1 held by the consumer + 1
      being staged never exceeds the pool, so a yielded view is valid
      until the NEXT ``next()`` call — consumers that keep chunks must
      copy (every in-repo consumer converts or reduces immediately).
    * **Exceptions propagate**: a reader-thread failure re-raises in the
      consumer at the point of ``next()``.
    * **Early shutdown**: ``close()`` (also called on ``__del__`` and by
      the streaming consumers' ``finally``) stops the reader thread and
      frees the buffers even mid-stream — an aborted fit leaks nothing.

    Yielded arrays are read-only views into the staging buffers, matching
    the read-only memmap semantics of the synchronous path.
    """

    _SENTINEL = object()

    def __init__(self, store: "RunStore", chunk_rows: int, *,
                 dtype: np.dtype | None, row_range: tuple[int, int] | None,
                 col_range: tuple[int, int] | None = None,
                 col_range_x: tuple[int, int] | None = None,
                 depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._store = store
        self._chunk_rows = chunk_rows
        self._dtype = _normalize_dtype(dtype)
        self._row_range = row_range
        self._col_range = col_range
        self._col_range_x = col_range_x
        self._depth = depth
        self.stats = PrefetchStats()
        # Hoisted global-metric instruments (one dict lookup each, here,
        # instead of one per staged chunk on the hot path).
        _m = obs.get_metrics()
        self._m_bytes = _m.counter("bytes_staged")
        self._m_chunks = _m.counter("chunks_staged")
        self._m_read_stall = _m.counter("read_stall_s")
        self._m_compute_stall = _m.counter("compute_stall_s")
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._bufs: list[tuple[np.ndarray, np.ndarray]] | None = None
        self._done = False

    # -- iterator protocol ---------------------------------------------------
    def __iter__(self) -> "ChunkPrefetcher":
        return self

    def _start(self) -> None:
        dt_x = self._dtype or self._store.dtype_x
        dt_y = self._dtype or self._store.dtype_y
        clo, chi = (self._col_range if self._col_range is not None
                    else (0, self._store.t))
        xlo, xhi = (self._col_range_x if self._col_range_x is not None
                    else (0, self._store.p))
        n_buf = self._depth + 2
        self._bufs = [
            (np.empty((self._chunk_rows, xhi - xlo), dt_x),
             np.empty((self._chunk_rows, chi - clo), dt_y))
            for _ in range(n_buf)]
        self._thread = threading.Thread(
            target=self._reader, name="runstore-prefetch", daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Stop-aware bounded put; returns False when closed mid-stream.
        Time spent blocked here is compute-stall (queue full = the device
        side is behind) — one ``prefetch.compute_stall`` span, from which
        ``stats.compute_stall_s`` is derived."""
        try:
            self._queue.put_nowait(item)
            return True
        except queue.Full:
            pass
        with obs.timed("prefetch.compute_stall") as t:
            ok = False
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.05)
                    ok = True
                    break
                except queue.Full:
                    continue
        if ok:
            self.stats.compute_stall_s += t.dur_s
            self._m_compute_stall.inc(t.dur_s)
        return ok

    def _reader(self) -> None:
        """Walk the synchronous iterator, staging each chunk into the pool.

        Resilience: when the backing store carries a ``fault_policy``, a
        transient mid-stream failure does not kill the stream — the
        reader backs off (deterministic jitter, see
        ``repro.resilience.policy``) and RESTARTS the synchronous
        iterator at the first unconsumed chunk.  Chunks are uniformly
        ``chunk_rows`` rows except the ragged tail, so chunk ``seq``
        always starts at global row ``lo + seq·chunk_rows`` and the
        restarted stream yields the identical remaining sequence —
        bit-identity survives the retry.  The attempt counter resets on
        every staged chunk, so only *consecutive* failures exhaust the
        policy; a give-up (or any permanent error) propagates to the
        consumer exactly as before.
        """
        from repro.resilience.policy import classify_default

        policy = getattr(self._store, "fault_policy", None)
        lo, hi = (self._row_range if self._row_range is not None
                  else (0, self._store.n_total))
        metrics = obs.get_metrics()
        seq = 0
        attempt = 0
        burst_start = None
        try:
            while True:
                try:
                    for X_c, Y_c in self._store._iter_chunks_sync(
                            self._chunk_rows, self._dtype,
                            lo + seq * self._chunk_rows, hi,
                            self._col_range, self._col_range_x):
                        if self._stop.is_set():
                            return
                        bx, by = self._bufs[seq % len(self._bufs)]
                        m = X_c.shape[0]
                        # The staging copy (memmap page-in + dtype
                        # conversion) is one ``prefetch.stage`` span;
                        # bytes_staged derives from the same region.
                        with obs.timed("prefetch.stage", chunk=seq) as t:
                            np.copyto(bx[:m], X_c)
                            np.copyto(by[:m], Y_c)
                            staged = bx[:m].nbytes + by[:m].nbytes
                            t.set(bytes=staged)
                        vx, vy = bx[:m].view(), by[:m].view()
                        vx.flags.writeable = False
                        vy.flags.writeable = False
                        self.stats.bytes_staged += staged
                        self._m_bytes.inc(staged)
                        if not self._put((vx, vy)):
                            return
                        seq += 1
                        attempt = 0
                        burst_start = None
                    break
                except BaseException as exc:         # noqa: BLE001
                    if self._stop.is_set():
                        return
                    if policy is None or not classify_default(exc):
                        raise
                    attempt += 1
                    now = policy.clock()
                    if burst_start is None:
                        burst_start = now
                    out_of_time = (policy.deadline_s is not None and
                                   now - burst_start >= policy.deadline_s)
                    if attempt >= policy.max_attempts or out_of_time:
                        metrics.counter("io_giveups",
                                        op="prefetch.read").inc()
                        obs.instant("retry.giveup", op="prefetch.read",
                                    attempt=attempt)
                        raise
                    metrics.counter("io_retries", op="prefetch.read").inc()
                    delay = policy.delay_for("prefetch.read", attempt)
                    with obs.span("retry.backoff", op="prefetch.read",
                                  attempt=attempt,
                                  delay_s=round(delay, 6)):
                        if delay > 0.0:
                            policy.sleep(delay)
            self._put(self._SENTINEL)
        except BaseException as exc:                 # noqa: BLE001
            self._put(exc)

    def __next__(self):
        if self._done:
            raise StopIteration
        if self._thread is None:
            self._start()
        # Consumer-side block on an empty queue: one ``prefetch.wait``
        # span, from which ``stats.read_stall_s`` is derived.
        with obs.timed("prefetch.wait") as t:
            item = self._queue.get()
        self.stats.read_stall_s += t.dur_s
        self._m_read_stall.inc(t.dur_s)
        if item is self._SENTINEL:
            self.close()
            raise StopIteration
        if isinstance(item, BaseException):
            self.close()
            raise item
        self.stats.chunks += 1
        self._m_chunks.inc()
        return item

    def close(self) -> None:
        """Stop the reader, drain the queue, release the staging buffers
        (one ``prefetch.close`` span; a repeated close does nothing)."""
        self._done = True
        self._stop.set()
        if self._thread is None and self._bufs is None:
            return
        with obs.span("prefetch.close"):
            while True:                 # unblock a reader stuck on put()
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
            if self._thread is not None:
                self._thread.join(timeout=5.0)
                self._thread = None
            self._bufs = None

    def __del__(self):
        try:
            self.close()
        except Exception:               # interpreter teardown
            pass


def _read_npy_header(path: str) -> tuple[tuple[int, ...], np.dtype]:
    """Shape/dtype from the .npy header alone (no data page-in)."""
    with open(path, "rb") as f:
        version = np.lib.format.read_magic(f)
        shape, _, dtype = np.lib.format._read_array_header(f, version)
    return shape, dtype


class RunStore:
    """On-disk (X, Y) row store — write runs once, stream them many times.

    Writing (builds/extends the manifest)::

        store = RunStore.create(path, n_folds=5)
        store.write(X_run1, Y_run1, "ses-001_run-1")
        store.write(X_run2, Y_run2, "ses-001_run-2")

    Streaming (read-only memmaps; resident set = one chunk)::

        store = RunStore.open(path)
        for X_c, Y_c in store.iter_chunks(chunk_rows=4096):
            ...                        # np.ndarray views, zero-copy

    ``materialize_synthetic`` writes a ``data.fmri`` subject once so
    benchmarks and tests can re-stream it without regenerating.
    """

    def __init__(self, root: str, *, n_folds: int, dtype_x: np.dtype,
                 dtype_y: np.dtype, p: int | None, t: int | None,
                 runs: list[RunEntry], writable: bool,
                 fault_policy: FaultPolicy | None = None):
        self.root = root
        self.n_folds = n_folds
        self.dtype_x = np.dtype(dtype_x)
        self.dtype_y = np.dtype(dtype_y)
        self.p = p
        self.t = t
        self.runs = runs
        self._writable = writable
        #: transient-fault retry policy for shard reads (None = no retry).
        self.fault_policy = fault_policy

    # -- construction --------------------------------------------------------
    @classmethod
    def create(cls, root: str, *, n_folds: int = 5,
               dtype: np.dtype | str = np.float32) -> "RunStore":
        """Start an empty, writable store at ``root`` (created if missing)."""
        os.makedirs(root, exist_ok=True)
        # A crashed writer leaves `*.tmp-*` shard stubs / a manifest tmp
        # behind; reap them (age-gated) before validating emptiness.
        cleanup.reap_stale_staging(root)
        if os.path.exists(os.path.join(root, MANIFEST_NAME)):
            raise StoreError(f"store already exists at {root}; use open()")
        store = cls(root, n_folds=n_folds, dtype_x=np.dtype(dtype),
                    dtype_y=np.dtype(dtype), p=None, t=None, runs=[],
                    writable=True)
        store._write_manifest()
        return store

    @classmethod
    def open(cls, root: str, *, fault_policy: FaultPolicy | None = None
             ) -> "RunStore":
        """Open read-only and validate the manifest against the shards.

        ``fault_policy`` arms transient-fault retry on every subsequent
        shard mmap and on the prefetcher's chunk stream (see
        ``repro.resilience``); omitted, reads fail fast as before.
        """
        path = os.path.join(root, MANIFEST_NAME)
        if not os.path.exists(path):
            raise StoreError(f"no {MANIFEST_NAME} under {root}")
        with open(path) as f:
            m = json.load(f)
        if m.get("version") != _MANIFEST_VERSION:
            raise StoreError(f"unsupported manifest version {m.get('version')}")
        runs = [RunEntry(run_id=r["run_id"], row_offset=r["row_offset"],
                         n_rows=r["n_rows"]) for r in m["runs"]]
        store = cls(root, n_folds=m["n_folds"],
                    dtype_x=_dtype_from_name(m["dtype_x"]),
                    dtype_y=_dtype_from_name(m["dtype_y"]),
                    p=m["p"], t=m["t"], runs=runs, writable=False,
                    fault_policy=fault_policy)
        store._validate()
        return store

    # -- manifest ------------------------------------------------------------
    def _write_manifest(self) -> None:
        payload = {
            "version": _MANIFEST_VERSION,
            "n_folds": self.n_folds,
            "dtype_x": self.dtype_x.name,
            "dtype_y": self.dtype_y.name,
            "p": self.p,
            "t": self.t,
            "n_total": self.n_total,
            # The fold split is part of the data contract: every consumer
            # (in-memory, chunked, sharded) derives the same contiguous
            # k-fold assignment from (n_total, n_folds).
            "runs": [{"run_id": r.run_id, "row_offset": r.row_offset,
                      "n_rows": r.n_rows} for r in self.runs],
        }
        tmp = os.path.join(self.root, MANIFEST_NAME + ".tmp")
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        os.replace(tmp, os.path.join(self.root, MANIFEST_NAME))

    def _validate(self) -> None:
        """Cross-check every shard header against the manifest."""
        offset = 0
        for r in self.runs:
            if r.row_offset != offset:
                raise StoreError(
                    f"run {r.run_id!r}: row_offset {r.row_offset} overlaps or "
                    f"gaps the preceding runs (expected {offset})")
            offset = r.row_end
            for path, want_cols, want_dtype, name in (
                    (_shard_paths(self.root, r.run_id)[0], self.p,
                     _storage_dtype(self.dtype_x), "X"),
                    (_shard_paths(self.root, r.run_id)[1], self.t,
                     _storage_dtype(self.dtype_y), "Y")):
                if not os.path.exists(path):
                    raise StoreError(f"run {r.run_id!r}: missing {name} shard "
                                     f"{os.path.basename(path)}")
                shape, dtype = _read_npy_header(path)
                if shape != (r.n_rows, want_cols):
                    raise StoreError(
                        f"run {r.run_id!r}: {name} shard shape {shape} != "
                        f"manifest ({r.n_rows}, {want_cols})")
                if dtype != want_dtype:
                    raise StoreError(
                        f"run {r.run_id!r}: {name} shard dtype {dtype} != "
                        f"manifest {want_dtype}")

    # -- writing -------------------------------------------------------------
    def write(self, X: np.ndarray, Y: np.ndarray, run_id: str) -> RunEntry:
        """Append one run's rows; shards land as ``.npy``, manifest updates."""
        if not self._writable:
            raise StoreError("store was open()'d read-only; create() to write")
        X = np.ascontiguousarray(X, dtype=self.dtype_x)
        Y = np.ascontiguousarray(Y, dtype=self.dtype_y)
        if X.ndim != 2 or Y.ndim != 2 or X.shape[0] != Y.shape[0]:
            raise StoreError(f"need matching 2-D row blocks, got X{X.shape} "
                             f"Y{Y.shape}")
        if any(r.run_id == run_id for r in self.runs):
            raise StoreError(f"run {run_id!r} already written")
        if self.p is None:
            self.p, self.t = X.shape[1], Y.shape[1]
        elif (X.shape[1], Y.shape[1]) != (self.p, self.t):
            raise StoreError(f"run {run_id!r}: columns ({X.shape[1]}, "
                             f"{Y.shape[1]}) != store ({self.p}, {self.t})")
        entry = RunEntry(run_id=run_id, row_offset=self.n_total,
                         n_rows=X.shape[0])
        x_path, y_path = _shard_paths(self.root, run_id)
        # Crash-safe shard landing: stage as `<shard>.tmp-<pid>` then
        # atomic-rename, manifest LAST — a killed writer leaves only a
        # reapable tmp stub, never a manifest pointing at a torn shard.
        for path, arr, dt in ((x_path, X, self.dtype_x),
                              (y_path, Y, self.dtype_y)):
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "wb") as f:
                np.save(f, arr.view(_storage_dtype(dt)))
            os.replace(tmp, path)
        self.runs.append(entry)
        self._write_manifest()
        return entry

    def materialize_synthetic(self, spec: SubjectSpec, *, seed: int = 0,
                              rows_per_run: int | None = None) -> "RunStore":
        """Write a ``data.fmri`` subject once, split into run-sized shards.

        The subject's ``(n, p)``/``(n, t)`` arrays are generated run by run
        (each run gets its own fold of the PRNG key) so even materialisation
        never holds the full subject resident — the generator mirrors how a
        real scanning session arrives: one run at a time.
        """
        import jax
        from repro.data import fmri

        # Best-effort sweep of staging left by a previous crashed
        # materialisation into the same root (age-gated; live writers
        # are younger than the gate).
        cleanup.reap_stale_staging(self.root)
        rows_per_run = rows_per_run or spec.n
        key = jax.random.PRNGKey(seed)
        lo = 0
        while lo < spec.n:
            hi = min(lo + rows_per_run, spec.n)
            run_key = jax.random.fold_in(key, lo)
            run_spec = dataclasses.replace(spec, n=hi - lo)
            X, Y, _ = fmri.generate(run_key, run_spec)
            self.write(np.asarray(X), np.asarray(Y),
                       f"{spec.subject}_rows-{lo:08d}")
            lo = hi
        return self

    # -- reading -------------------------------------------------------------
    @property
    def n_total(self) -> int:
        return self.runs[-1].row_end if self.runs else 0

    @property
    def shape(self) -> tuple[int, int, int]:
        """(n_total, p, t)."""
        if self.p is None:
            raise StoreError("empty store has no shape yet")
        return self.n_total, self.p, self.t

    def nbytes_resident(self) -> int:
        """Bytes an in-memory fit would hold resident: full X plus Y."""
        n, p, t = self.shape
        return n * (p * self.dtype_x.itemsize + t * self.dtype_y.itemsize)

    def _mmap_raw(self, r: RunEntry) -> tuple[np.ndarray, np.ndarray]:
        """The raw (no-retry) shard mapping — the fault-injection seam."""
        x_path, y_path = _shard_paths(self.root, r.run_id)
        return (np.load(x_path, mmap_mode="r").view(self.dtype_x),
                np.load(y_path, mmap_mode="r").view(self.dtype_y))

    def _mmap(self, r: RunEntry) -> tuple[np.ndarray, np.ndarray]:
        if self.fault_policy is None:
            return self._mmap_raw(r)
        return retry_call(lambda: self._mmap_raw(r), self.fault_policy,
                          "store.mmap")

    def iter_chunks(self, chunk_rows: int, *, dtype: np.dtype | str | None
                    = None, row_range: tuple[int, int] | None = None,
                    col_range: tuple[int, int] | None = None,
                    col_range_x: tuple[int, int] | None = None,
                    prefetch: bool = False, prefetch_depth: int = 2
                    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """Yield ``(X_chunk, Y_chunk)`` row batches in global row order.

        Batches are views into the read-only memmaps (ZERO host copies —
        including when ``dtype`` names the stored dtype) unless ``dtype``
        requests a real cast or a chunk spans a run boundary (then the
        spanning rows are concatenated into a fresh array of ``chunk_rows``
        rows at most — still O(chunk), never O(n)).  ``row_range=(lo, hi)``
        restricts the stream to a global row window — the hook the sharded
        accumulation uses to give each shard its own contiguous slice.

        ``col_range=(clo, chi)`` restricts ``Y`` to a target-column window:
        chunks arrive as ``(X (m, p), Y (m, chi−clo))`` with only the
        window's pages ever touched — the target-axis streaming hook
        (``repro.wholebrain``).  ``col_range=(0, 0)`` yields zero-width
        ``Y`` chunks, which is how the X-only Gram pass streams the rows
        without reading one byte of the (much wider) target shards.
        ``col_range_x`` is the (rare) mirror for ``X``.  Whole-brain fits
        never window REAL feature columns (p ≪ t is the whole regime);
        its one use is ``col_range_x=(0, 0)`` — a Y-only pass that reads
        zero bytes of the feature shards while a host-side chunk cache
        supplies the ``X`` rows captured during an earlier stream (the
        single-X-pass composition in ``repro.wholebrain.solver``).

        ``prefetch=True`` returns a ``ChunkPrefetcher`` instead: a
        background reader stages the NEXT chunk into a reusable host
        buffer (bounded queue of ``prefetch_depth``) while the caller
        processes the current one — same chunks, same order, same values,
        overlapped with compute.  The prefetcher exposes ``stats``
        (reader-stall vs compute-stall time) and ``close()`` for early
        shutdown; its reader thread starts lazily on first iteration, so
        building many shard streams up front costs nothing until each is
        consumed.
        """
        if chunk_rows < 1:
            raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
        lo, hi = row_range if row_range is not None else (0, self.n_total)
        if not 0 <= lo <= hi <= self.n_total:
            raise ValueError(f"row_range {row_range} outside "
                             f"[0, {self.n_total}]")
        if col_range is not None:
            clo, chi = col_range
            if not 0 <= clo <= chi <= (self.t or 0):
                raise ValueError(f"col_range {col_range} outside "
                                 f"[0, {self.t}]")
        if col_range_x is not None:
            xlo, xhi = col_range_x
            if not 0 <= xlo <= xhi <= (self.p or 0):
                raise ValueError(f"col_range_x {col_range_x} outside "
                                 f"[0, {self.p}]")
        dtype = _normalize_dtype(dtype)
        if prefetch:
            return ChunkPrefetcher(self, chunk_rows, dtype=dtype,
                                   row_range=(lo, hi), col_range=col_range,
                                   col_range_x=col_range_x,
                                   depth=prefetch_depth)
        return self._iter_chunks_sync(chunk_rows, dtype, lo, hi, col_range,
                                      col_range_x)

    def _iter_chunks_sync(self, chunk_rows: int, dtype: np.dtype | None,
                          lo: int, hi: int,
                          col_range: tuple[int, int] | None = None,
                          col_range_x: tuple[int, int] | None = None
                          ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        pending_x: list[np.ndarray] = []
        pending_y: list[np.ndarray] = []
        pending = 0

        def cast(a: np.ndarray) -> np.ndarray:
            # Aligned-dtype fast path: hand back the memmap view itself —
            # no host copy for the common store-dtype == compute-dtype case.
            if dtype is None or a.dtype == dtype:
                return a
            return a.astype(dtype)

        for r in self.runs:
            if r.row_end <= lo or r.row_offset >= hi:
                continue
            Xm, Ym = self._mmap(r)
            if col_range is not None:
                # Column window of the memmap: a strided VIEW — zero-copy,
                # and reads fault in only the window's pages per row.
                Ym = Ym[:, col_range[0]:col_range[1]]
            if col_range_x is not None:
                Xm = Xm[:, col_range_x[0]:col_range_x[1]]
            s_lo = max(lo, r.row_offset) - r.row_offset
            s_hi = min(hi, r.row_end) - r.row_offset
            pos = s_lo
            while pos < s_hi:
                take = min(chunk_rows - pending, s_hi - pos)
                if pending:
                    pending_x.append(Xm[pos:pos + take])
                    pending_y.append(Ym[pos:pos + take])
                    pending += take
                    if pending == chunk_rows:
                        yield (cast(np.concatenate(pending_x)),
                               cast(np.concatenate(pending_y)))
                        pending_x, pending_y, pending = [], [], 0
                elif take == chunk_rows:
                    yield cast(Xm[pos:pos + take]), cast(Ym[pos:pos + take])
                else:
                    pending_x = [Xm[pos:pos + take]]
                    pending_y = [Ym[pos:pos + take]]
                    pending = take
                pos += take
        if pending:     # ragged tail
            yield (cast(np.concatenate(pending_x)),
                   cast(np.concatenate(pending_y)))

    def load(self, *, dtype: np.dtype | str | None = None
             ) -> tuple[np.ndarray, np.ndarray]:
        """Materialise the full (X, Y) — the in-memory reference path.

        Deliberately explicit: streaming consumers must never call this;
        it exists for parity tests and for ``BrainEncoder.fit(store=...)``
        when dispatch decides the problem fits the memory budget after all.
        """
        n, p, t = self.shape
        X = np.empty((n, p), self.dtype_x if dtype is None else dtype)
        Y = np.empty((n, t), self.dtype_y if dtype is None else dtype)
        for r in self.runs:
            Xm, Ym = self._mmap(r)
            X[r.row_offset:r.row_end] = Xm
            Y[r.row_offset:r.row_end] = Ym
        return X, Y


__all__ = ["ChunkPrefetcher", "PrefetchStats", "RunStore", "RunEntry",
           "StoreError", "MANIFEST_NAME"]
