"""Backbone features as a streamed fit source: transcript tokens through a
language-model backbone, pooled to TRs, delayed on the device.

A subject is a targets ``RunStore`` (zero-width X, the fMRI targets Y)
with one transcript per run beside it: the run's token ids and, for each
TR, the index of the last token at or before the TR's end (−1 before the
run's first token).  ``BackboneSource`` presents it to
``BrainEncoder.fit_chunks`` like a ``RunStore`` of the delayed features:

1. **Extract.**  Each run is padded to a whole number of SSD chunks and
   packed, in store order, into rows of ``pack_tokens`` tokens; batches of
   rows run through the backbone (``HybridLM.features``), each row's runs
   kept apart by their positions (``pos``).  A TR's feature is the
   residual stream at its last token, zero before the run's first token.
   The per-TR features of the whole subject stay on the device.
2. **Delay.**  Each fit chunk of TR rows is built on the device from that
   buffer: TR t carries the features of TRs t−1 … t−lags of its own run,
   zero where that lies before the run's start (a chunk reads ``lags``
   rows of halo before its first row).
3. **Stream.**  Chunks go to the fold statistics with the matching rows of
   Y, read from the store; the fit then runs exactly as a store's does.

The batch of runs is sized from the device-memory budget, as
``chunk_rows`` is.  The fit plans its own device memory against the whole
budget, so where a budget is set the weights leave the device after
extraction and come back at the next fit's start (span
``extract.weights_h2d``).  The extraction runs when the fit opens its
stream, before the fold statistics start.

Spans ``fit.extract`` (``runs``, ``tokens``, ``pad_tokens``, ``batches``)
and ``extract.batch``; counters ``extract_tokens``,
``extract_pad_tokens``, ``moe_routed_pairs``; histogram
``moe_held_load_max`` (per batch, the busiest held expert's pairs over the
mean).  Device scopes ``backbone``, ``ssd``, ``attention``,
``moe_routed``, ``shared_mlp``, ``delay_embed``.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.data.store import RunStore

TRANSCRIPT = "{run_id}.transcript.npz"


@dataclasses.dataclass(frozen=True)
class Transcript:
    """One run's stimulus: token ids and each TR's last token index."""

    tokens: np.ndarray      # (n_tokens,) int32
    tr_end: np.ndarray      # (n_tr,) int32, −1 before the first token


def write_subject(root: str, transcripts: list[Transcript],
                  targets: list[np.ndarray], n_folds: int) -> RunStore:
    """A targets store of one run per transcript, transcripts beside it."""
    store = RunStore.create(root, n_folds=n_folds)
    for i, (tr, Y) in enumerate(zip(transcripts, targets)):
        run_id = f"run-{i:04d}"
        store.write(np.zeros((len(Y), 0), np.float32), Y, run_id)
        np.savez(os.path.join(root, TRANSCRIPT.format(run_id=run_id)),
                 tokens=tr.tokens, tr_end=tr.tr_end)
    return RunStore.open(root)


def read_transcripts(store: RunStore) -> list[Transcript]:
    out = []
    for r in store.runs:
        with np.load(os.path.join(store.root,
                                  TRANSCRIPT.format(run_id=r.run_id))) as z:
            tr = Transcript(tokens=z["tokens"].astype(np.int32),
                            tr_end=z["tr_end"].astype(np.int32))
        if len(tr.tr_end) != r.n_rows:
            raise ValueError(f"run {r.run_id!r}: {len(tr.tr_end)} TRs in its "
                             f"transcript, {r.n_rows} rows of targets")
        out.append(tr)
    return out


# -- packing ---------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Packing:
    """Runs packed into fixed rows, and where each TR's feature comes from.

    ``tokens``/``pos``: (rows, pack_tokens); ``src[b]``: for each TR slot
    of batch b, the flat token index within the batch (−1: zero feature);
    ``dst[b]``: the TR's row in the feature buffer (its global TR index
    plus ``lags``; the buffer's last row takes unused slots)."""

    tokens: np.ndarray
    pos: np.ndarray
    src: np.ndarray          # (batches, slots) int32
    dst: np.ndarray          # (batches, slots) int32
    rows_per_batch: int
    span: int                # longest run, in SSD chunks
    n_tokens: int
    pad_tokens: int

    @property
    def batches(self) -> int:
        return self.src.shape[0]


def pack_tokens(chunk: int, longest: int) -> int:
    """Tokens in a packed row: 64 SSD chunks, or the longest run."""
    return max(64 * chunk, -(-longest // chunk) * chunk)


def pack(transcripts: list[Transcript], chunk: int, rows_per_batch: int,
         lags: int) -> Packing:
    """Pack runs in store order, each padded to whole chunks, into rows."""
    lens = [max(chunk, -(-len(t.tokens) // chunk) * chunk)
            for t in transcripts]
    S = pack_tokens(chunk, max(lens))
    placed, row, cur = [], 0, 0
    for L in lens:
        if cur + L > S:
            row, cur = row + 1, 0
        placed.append((row, cur))
        cur += L
    n_rows = -(-(row + 1) // rows_per_batch) * rows_per_batch
    tokens = np.zeros((n_rows, S), np.int32)
    pos = np.tile(np.arange(S, dtype=np.int32), (n_rows, 1))
    per_batch: list[list[tuple[np.ndarray, np.ndarray]]] = [
        [] for _ in range(n_rows // rows_per_batch)]
    tr0 = 0
    for t, (r, o), L in zip(transcripts, placed, lens):
        n = len(t.tokens)
        tokens[r, o:o + n] = t.tokens
        pos[r, o:o + L] = np.arange(L, dtype=np.int32)
        if o + L < S:        # what follows in the row starts afresh
            pos[r, o + L:] = np.arange(S - o - L, dtype=np.int32)
        b, rb = divmod(r, rows_per_batch)
        src = np.where(t.tr_end >= 0, rb * S + o + t.tr_end, -1)
        dst = lags + tr0 + np.arange(len(t.tr_end))
        per_batch[b].append((src.astype(np.int32), dst.astype(np.int32)))
        tr0 += len(t.tr_end)
    slots = max(sum(len(s) for s, _ in pb) for pb in per_batch)
    slots = -(-slots // 512) * 512
    src = np.full((len(per_batch), slots), -1, np.int32)
    dst = np.full((len(per_batch), slots), lags + tr0, np.int32)
    for b, pb in enumerate(per_batch):
        if pb:
            s = np.concatenate([a for a, _ in pb])
            d = np.concatenate([a for _, a in pb])
            src[b, :len(s)], dst[b, :len(d)] = s, d
    n_tok = sum(len(t.tokens) for t in transcripts)
    return Packing(tokens=tokens, pos=pos, src=src, dst=dst,
                   rows_per_batch=rows_per_batch, span=max(lens) // chunk,
                   n_tokens=n_tok, pad_tokens=tokens.size - n_tok)


def positions_in_run(runs) -> np.ndarray:
    """(n,) each TR's index within its run, for ``RunStore.runs``."""
    return np.concatenate([np.arange(r.n_rows, dtype=np.int32)
                           for r in runs])


# -- device work -----------------------------------------------------------------

def delay_rows(F: jax.Array, start: jax.Array, pos: jax.Array, lags: int,
               rows: int) -> jax.Array:
    """TR rows [start, start + rows) of the delayed features.

    F: (lags + n + 1, d) per-TR features, TR r at row ``lags + r``; pos:
    (rows,) each TR's index within its run.  Lag j of TR t is F of TR t−j
    where ``pos ≥ j``, else zero."""
    win = jax.lax.dynamic_slice_in_dim(F, start, rows + lags)
    parts = [jnp.where((pos >= j)[:, None], win[lags - j:lags - j + rows], 0.0)
             for j in range(1, lags + 1)]
    return jnp.concatenate(parts, axis=1)


def bytes_per_token(cfg) -> int:
    """Device bytes one packed token takes while a batch runs: the widest
    layer's f32 working set (a Mamba-2 layer's projections, conv, SSD terms
    and gated norm, or the MoE's pair buffers when every held pick
    overflows the fast buffer), with room for XLA's temporaries."""
    s, d = cfg.ssm, cfg.d_model
    d_inner = s.expand * d
    H = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.d_state
    mamba = 4 * (3 * conv_ch + 6 * d_inner + 2 * H * s.d_state * s.head_dim
                 // s.chunk + 4 * d)
    m = cfg.moe
    pairs = min(m.top_k, len(m.held_experts))
    moe = 4 * pairs * (d + 3 * (m.d_expert or cfg.d_ff) + d) + 4 * 4 * d
    return 2 * max(mamba, moe)


class Extractor:
    """A backbone's weights and compiled extraction, kept across fits.

    ``budget`` (device bytes, None: unlimited) sizes the batch of packed
    rows; with a budget the weights are on the device only while they
    extract, since the fit that follows plans against the whole budget."""

    def __init__(self, cfg, params, *, lags: int = 4,
                 budget: int | None = None):
        from repro.models import build_model

        self.cfg = cfg
        self.model = build_model(cfg)
        self.lags = lags
        leaves = jax.tree_util.tree_leaves(params)
        self.weight_bytes = int(sum(a.size * a.dtype.itemsize for a in leaves))
        self.budget = budget
        self.resident = budget is None
        self._host = None if self.resident else jax.device_get(params)
        self._params = jax.device_put(params)
        self._run = jax.jit(self._batch, static_argnames=("span",),
                            donate_argnums=(3,))
        self._delay = jax.jit(self._delay_rows, static_argnames=("rows",))

    def rows_per_batch(self, pack_len: int, n_tr: int) -> int:
        """Packed rows per batch that fit beside the weights and the
        per-TR feature buffer in half of what the budget leaves."""
        if self.budget is None:
            return 1
        free = (self.budget - self.weight_bytes
                - 4 * (n_tr + self.lags + 1) * self.cfg.d_model)
        per_row = pack_len * bytes_per_token(self.cfg)
        return max(1, free // 2 // per_row)

    def _batch(self, params, tokens, pos, F, src, dst, *, span):
        h, counts = self.model.features(params, tokens, pos, span)
        hf = h.reshape(-1, h.shape[-1])
        pooled = jnp.where((src >= 0)[:, None],
                           jnp.take(hf, jnp.maximum(src, 0), axis=0), 0.0)
        return F.at[dst].set(pooled), counts

    def _delay_rows(self, F, start, pos, *, rows):
        with jax.named_scope("delay_embed"):
            return delay_rows(F, start, pos, self.lags, rows)

    def params(self):
        """The weights on the device, brought back if they were sent off."""
        if self._params is None:
            nbytes = self.weight_bytes
            with obs.transfer("extract.weights_h2d", "h2d_bytes", nbytes,
                              site="weights"):
                self._params = jax.device_put(self._host)
                jax.block_until_ready(self._params)
        return self._params

    def release(self) -> None:
        """After extraction: free the device copy unless it stays."""
        if not self.resident:
            self._params = None

    def extract(self, transcripts: list[Transcript], n_tr: int) -> jax.Array:
        """(lags + n_tr + 1, d) f32 per-TR features of the runs in order
        (rows ``lags … lags + n_tr − 1``; zero halo and a spare last row)."""
        Q = self.cfg.ssm.chunk
        longest = max(len(t.tokens) for t in transcripts)
        S = pack_tokens(Q, max(Q, longest))
        pk = pack(transcripts, Q, self.rows_per_batch(S, n_tr), self.lags)
        self.last = {"batches": pk.batches, "tokens": pk.n_tokens,
                     "pad_tokens": pk.pad_tokens}
        m = obs.get_metrics()
        with obs.span("fit.extract", runs=len(transcripts),
                      tokens=pk.n_tokens, pad_tokens=pk.pad_tokens,
                      batches=pk.batches):
            params = self.params()
            F = jnp.zeros((self.lags + n_tr + 1, self.cfg.d_model),
                          jnp.float32)
            counts = []
            R = pk.rows_per_batch
            for b in range(pk.batches):
                with obs.span("extract.batch", batch=b):
                    F, c = self._run(params, pk.tokens[b * R:(b + 1) * R],
                                     pk.pos[b * R:(b + 1) * R], F,
                                     pk.src[b], pk.dst[b], span=pk.span)
                    counts.append(c)
                    if obs.current() is not None:
                        jax.block_until_ready(F)
            counts = [np.asarray(c, np.float64) for c in counts]
            m.counter("extract_tokens").inc(pk.n_tokens)
            m.counter("extract_pad_tokens").inc(pk.pad_tokens)
            load = m.histogram("moe_held_load_max")
            for c in counts:
                m.counter("moe_routed_pairs").inc(float(c.sum()))
                if c.size and c.mean() > 0:
                    load.observe(float(c.max() / c.mean()))
            self.release()
        return F

    def delayed(self, F: jax.Array, start: int, pos: np.ndarray) -> jax.Array:
        return self._delay(F, jnp.int32(start), pos, rows=len(pos))


class BackboneSource:
    """A subject's delayed backbone features, read like a ``RunStore`` by
    ``BrainEncoder.fit_chunks``: ``shape``, ``n_folds`` and
    ``iter_chunks``."""

    def __init__(self, extractor: Extractor, store: RunStore,
                 transcripts: list[Transcript] | None = None):
        self.extractor = extractor
        self.store = store
        self.transcripts = (read_transcripts(store) if transcripts is None
                            else transcripts)
        self.n_folds = store.n_folds
        n, _, t = store.shape
        self.shape = (n, extractor.lags * extractor.cfg.d_model, t)

    def features(self) -> jax.Array:
        """The undelayed per-TR feature buffer (see ``Extractor.extract``)."""
        return self.extractor.extract(self.transcripts, self.shape[0])

    def iter_chunks(self, chunk_rows: int, *, row_range=None,
                    prefetch: bool = False, prefetch_depth: int = 2):
        return _Stream(self, chunk_rows, row_range, prefetch, prefetch_depth)


class _Stream:
    """Extracts when opened, then yields (device X chunk, host Y chunk)."""

    def __init__(self, src: BackboneSource, chunk_rows: int, row_range,
                 prefetch: bool, depth: int):
        self._src = src
        self._rows = chunk_rows
        lo, hi = row_range if row_range is not None else (0, src.shape[0])
        self._lo, self._hi = lo, hi
        self._y = src.store.iter_chunks(chunk_rows, row_range=(lo, hi),
                                        col_range_x=(0, 0),
                                        prefetch=prefetch,
                                        prefetch_depth=depth)
        self.stats = getattr(self._y, "stats", None)
        self._pos = positions_in_run(src.store.runs)
        self._F = src.features()
        self._at = lo

    def __iter__(self):
        return self

    def __next__(self):
        if self._at >= self._hi:
            self.close()
            raise StopIteration
        _, Y = next(self._y)
        m = len(Y)
        X = self._src.extractor.delayed(self._F, self._at,
                                        self._pos[self._at:self._at + m])
        self._at += m
        return X, Y

    def close(self) -> None:
        self._F = None
        if hasattr(self._y, "close"):
            self._y.close()


__all__ = ["BackboneSource", "Extractor", "Transcript", "bytes_per_token",
           "delay_rows", "pack", "positions_in_run", "read_transcripts",
           "write_subject"]
