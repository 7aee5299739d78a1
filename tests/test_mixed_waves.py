"""The mixed-wave packer + its bit-identity contract.

The fleet front-end packs scored AND unscored requests from different
tenants into the same fixed-shape waves (per-row request one-hot →
per-slot Pearson sums from one compiled program).  These tests lock the
two halves down:

* ``plan_mixed_waves`` invariants — complete in-order coverage, slot
  bounds, early close on slot exhaustion — on a fixed grid;
* the contract the whole tier stands on: for ANY mix of scored/unscored
  ragged requests, every wave-bucket ladder, and every packing cut
  (including the nearly-all-padding tail wave), the packed serve is
  BIT-identical — predictions and Pearson r — to serving each request
  alone.  Exhaustive small grid always runs; hypothesis widens the search
  when the library is installed.
"""
import numpy as np
import pytest

from repro.encoding import BrainEncoder
from repro.serving_encoders import (
    EncoderRegistry, EncoderService, PredictRequest, ServiceError,
    plan_mixed_waves, reference_serve,
)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

P, T = 12, 7


@pytest.fixture(scope="module")
def fleet_dir(tmp_path_factory):
    """Two small fitted bundles sharing (p, t) — the packer's tenants."""
    import jax
    import jax.numpy as jnp

    root = tmp_path_factory.mktemp("mixed_fleet")
    for i, name in enumerate(("m0", "m1")):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(i), 3)
        X = jax.random.normal(k1, (90, P), jnp.float32)
        W = jax.random.normal(k2, (P, T), jnp.float32)
        Y = X @ W + 0.1 * jax.random.normal(k3, (90, T), jnp.float32)
        BrainEncoder(n_folds=3).fit(X, Y).save(str(root / name))
    return root


def _registry(fleet_dir):
    reg = EncoderRegistry()
    reg.add("m0", str(fleet_dir / "m0"))
    reg.add("m1", str(fleet_dir / "m1"))
    return reg


def _requests(row_sizes, scored_flags, models=None, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (rows, scored) in enumerate(zip(row_sizes, scored_flags)):
        X = rng.standard_normal((rows, P)).astype(np.float32)
        Y = (rng.standard_normal((rows, T)).astype(np.float32)
             if scored else None)
        model = (models[i] if models else "m0")
        reqs.append(PredictRequest(model=model, features=X, targets=Y,
                                   tenant=f"tenant-{i % 3}"))
    return reqs


def _assert_bit_identical(fleet_dir, reqs, buckets, score_slots=2):
    packed_svc = EncoderService(_registry(fleet_dir), wave_buckets=buckets,
                                score_slots=score_slots)
    ref_svc = EncoderService(_registry(fleet_dir), wave_buckets=buckets,
                             score_slots=score_slots)
    packed = packed_svc.serve(reqs)
    ref = reference_serve(ref_svc, reqs)
    for i, (got, want) in enumerate(zip(packed, ref)):
        assert got.error is None and want.error is None
        assert np.array_equal(got.predictions, want.predictions), \
            f"request {i}: packed predictions diverge from serving alone"
        assert (got.pearson_r is None) == (want.pearson_r is None)
        if got.pearson_r is not None:
            assert np.array_equal(got.pearson_r, want.pearson_r), \
                f"request {i}: packed Pearson r diverges from serving alone"
    # Packing the mix must cost one compile per wave bucket USED — never
    # one per scored/unscored combination.
    assert packed_svc.compile_count == len(packed_svc.stats.per_bucket)


# -- planner invariants ------------------------------------------------------

def _check_plan(plan, req_rows, scored, score_slots):
    consumed = [0] * len(req_rows)
    cursor = 0                               # requests fill in arrival order
    for wave in plan:
        assert 0 < wave.fill <= wave.rows
        pos, slots = 0, set()
        for seg in wave.segments:
            assert seg.wave_lo == pos        # contiguous from offset 0
            assert seg.req >= cursor
            cursor = seg.req
            assert seg.req_lo == consumed[seg.req]
            consumed[seg.req] = seg.req_hi
            pos += seg.req_hi - seg.req_lo
            if scored[seg.req]:
                assert seg.slot is not None and seg.slot not in slots
                slots.add(seg.slot)
            else:
                assert seg.slot is None
        assert pos == wave.fill
        assert len(slots) <= score_slots
    assert consumed == list(req_rows)        # complete coverage


@pytest.mark.parametrize("score_slots", [1, 2, 4])
def test_plan_covers_all_rows_in_order(score_slots):
    req_rows = [5, 1, 17, 8, 3, 30, 2]
    scored = [True, False, True, True, False, True, True]
    plan = plan_mixed_waves(req_rows, scored, lambda rem: 8, score_slots)
    _check_plan(plan, req_rows, scored, score_slots)


def test_plan_slot_exhaustion_closes_wave_early():
    # 4 one-row scored requests into 16-row waves with 2 slots: the wave
    # must close after 2 scored requests even though 14 rows are free.
    plan = plan_mixed_waves([1, 1, 1, 1], [True] * 4, lambda rem: 16, 2)
    assert [w.fill for w in plan] == [2, 2]
    assert all(w.rows == 16 for w in plan)   # the tail is padding


def test_plan_all_padding_tail():
    # 17 rows on an 8-ladder: the tail wave carries 1 real row + 7 pad.
    plan = plan_mixed_waves([17], [True], lambda rem: 8, 1)
    assert [w.fill for w in plan] == [8, 8, 1]
    _check_plan(plan, [17], [True], 1)


def test_plan_rejects_zero_slots():
    with pytest.raises(ServiceError, match="score_slots"):
        plan_mixed_waves([4], [True], lambda rem: 8, 0)


# -- bit-identity: fixed grid (always runs) ----------------------------------

LADDERS = [(8,), (8, 32), (4, 16, 64)]


@pytest.mark.parametrize("buckets", LADDERS)
def test_mixed_pack_bit_identical_grid(fleet_dir, buckets):
    # Ragged sizes straddling every bucket boundary; scored/unscored
    # interleaved; two models so waves regroup per model.
    rows = [3, 20, 1, 33, 8, 5]
    scored = [True, False, True, True, False, True]
    models = ["m0", "m0", "m1", "m0", "m1", "m0"]
    reqs = _requests(rows, scored, models, seed=buckets[0])
    _assert_bit_identical(fleet_dir, reqs, buckets)


def test_mixed_pack_bit_identical_all_padding_tail(fleet_dir):
    # One 9-row scored request on (8,): the tail wave is 1 real row + 7
    # zero rows — the padding must be absorbed exactly (±0 adds) by the
    # sequential per-slot sum chain.
    reqs = _requests([9], [True])
    _assert_bit_identical(fleet_dir, reqs, (8,))


def test_mixed_pack_bit_identical_slot_pressure(fleet_dir):
    # More scored requests than slots per wave → early closes, carries
    # chained across many waves.
    rows = [2, 3, 2, 4, 2, 5]
    reqs = _requests(rows, [True] * 6)
    _assert_bit_identical(fleet_dir, reqs, (8, 16), score_slots=1)


def test_scored_request_spanning_many_waves(fleet_dir):
    # One scored request cut across 5 waves: its Pearson sums must chain
    # through sums_in from wave to wave, staying one sequential f32 chain.
    reqs = _requests([37], [True])
    _assert_bit_identical(fleet_dir, reqs, (8,))


# -- bit-identity: hypothesis widening (gated on availability) ---------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        rows=st.lists(st.integers(min_value=1, max_value=40),
                      min_size=1, max_size=6),
        scored=st.lists(st.booleans(), min_size=6, max_size=6),
        which=st.lists(st.integers(min_value=0, max_value=1),
                       min_size=6, max_size=6),
        ladder=st.sampled_from(LADDERS),
        slots=st.integers(min_value=1, max_value=3),
    )
    def test_mixed_pack_bit_identical_property(fleet_dir, rows, scored,
                                               which, ladder, slots):
        n = len(rows)
        reqs = _requests(rows, scored[:n],
                         [f"m{w}" for w in which[:n]], seed=n)
        _assert_bit_identical(fleet_dir, reqs, ladder, score_slots=slots)


# -- staging: reused host buffers ---------------------------------------------

def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32)


def _fresh_wave(wave, blocks, targets, req_sums, p, t, s):
    """The wave inputs as the service built them before it reused host
    buffers: fresh zero arrays with the segments copied in."""
    X = np.zeros((wave.rows, p), np.float32)
    Yt = np.zeros((wave.rows, t), np.float32)
    onehot = np.zeros((wave.rows, s), np.float32)
    sums_in = np.zeros((s, 5, t), np.float32)
    for seg in wave.segments:
        dst = slice(seg.wave_lo, seg.wave_lo + seg.req_hi - seg.req_lo)
        X[dst] = blocks[seg.req][seg.req_lo:seg.req_hi]
        if seg.slot is not None:
            Yt[dst] = targets[seg.req][seg.req_lo:seg.req_hi]
            onehot[dst, seg.slot] = 1.0
            sums_in[seg.slot] = req_sums[seg.req]
    return X, Yt, onehot, sums_in


def _capture(svc):
    """Record every wave program's inputs (copied as it is called: on the
    CPU a device input is the host buffer) and its sums."""
    calls = []
    program = svc._predict_mixed

    def wrapped(X, Yt, onehot, sums_in, *rest):
        inputs = tuple(np.array(a) for a in (X, Yt, onehot, sums_in))
        P, sums_out = program(X, Yt, onehot, sums_in, *rest)
        calls.append((inputs, sums_out))
        return P, sums_out

    svc._predict_mixed = wrapped
    return calls


def _replay_fresh(svc, reqs, calls):
    """Each wave's inputs by ``_fresh_wave``, in the service's order (per
    model in arrival order), the sums chained through the captured
    program outputs; ``calls`` is consumed."""
    groups = {}
    for i, r in enumerate(reqs):
        groups.setdefault(r.model, []).append(i)
    s = svc.score_slots
    for idxs in groups.values():
        blocks = [reqs[i].features for i in idxs]
        targets = [reqs[i].targets for i in idxs]
        scored = [tg is not None for tg in targets]
        t = svc.registry.bundle(reqs[idxs[0]].model).shape[1]
        req_sums = {j: np.zeros((5, t), np.float32)
                    for j, sc in enumerate(scored) if sc}
        plan = plan_mixed_waves([b.shape[0] for b in blocks], scored,
                                lambda rem: svc._next_wave(rem, None), s)
        for wave in plan:
            want = _fresh_wave(wave, blocks, targets, req_sums, P, T, s)
            got, sums_out = calls.pop(0)
            yield wave, got, want
            host = np.asarray(sums_out)
            for seg in wave.segments:
                if seg.slot is not None:
                    req_sums[seg.req] = host[seg.slot]


# Flushes served one after another by ONE service, so each reuses sets
# that earlier waves filled: full scored waves, then unscored and mixed
# segments over the stale targets, slot exhaustion (3 scored requests, 2
# slots), both buckets and two models of one shape (one pool key).
FLUSHES = [
    ([32, 32, 9], [True, True, True], ["m0", "m0", "m0"]),
    ([5, 20, 3], [False, True, False], ["m0", "m1", "m0"]),
    ([1, 1, 1, 30], [True, True, True, False], ["m1", "m1", "m1", "m1"]),
    ([7], [False], ["m0"]),
    ([40, 2], [True, False], ["m0", "m0"]),
]


def test_reused_staging_matches_fresh_zero_buffers(fleet_dir):
    svc = EncoderService(_registry(fleet_dir), wave_buckets=(8, 32),
                         score_slots=2)
    calls = _capture(svc)
    ref_svc = EncoderService(_registry(fleet_dir), wave_buckets=(8, 32),
                             score_slots=2)
    waves = 0
    for n, (rows, scored, models) in enumerate(FLUSHES):
        reqs = _requests(rows, scored, models, seed=100 + n)
        got = svc.serve(reqs)
        for wave, inputs, want in _replay_fresh(svc, reqs, calls):
            waves += 1
            for name, a, b in zip(("X", "Yt", "onehot", "sums_in"),
                                  inputs, want):
                assert np.array_equal(_bits(a), _bits(b)), \
                    f"flush {n}: {name} differs from a fresh build"
        assert not calls
        for res, alone in zip(got, reference_serve(ref_svc, reqs)):
            assert np.array_equal(res.predictions, alone.predictions)
            if alone.pearson_r is not None:
                assert np.array_equal(res.pearson_r, alone.pearson_r)
    # Every key was refilled over stale rows.
    assert waves > 2 * len(svc._staging)


def _counts():
    from repro import obs

    c = obs.snapshot()["counters"]
    return (c.get("wave_staging{outcome=allocated}", 0.0),
            c.get("wave_staging{outcome=reused}", 0.0))


class _AlignedNumpy:
    """numpy, with ``empty`` giving 64-byte aligned arrays, which the CPU
    backend's ``jnp.asarray`` always aliases rather than copies."""

    def __getattr__(self, name):
        return getattr(np, name)

    def empty(self, shape, dtype):
        dtype = np.dtype(dtype)
        n = int(np.prod(shape)) * dtype.itemsize
        raw = np.empty(n + 64, np.uint8)
        off = -raw.ctypes.data % 64
        return raw[off:off + n].view(dtype).reshape(shape)


def test_refill_waits_for_the_wave_built_before(fleet_dir, monkeypatch):
    """Eight unscored waves of one shape in one call, each wave program
    reading its features 50 ms after it is enqueued from a set the device
    array aliases: every set is refilled while the wave built in it is
    still pending.  Without the wait on that wave, later rows overwrite
    earlier waves' features."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    from repro.serving_encoders import service as service_module

    monkeypatch.setattr(service_module, "np", _AlignedNumpy())
    svc = EncoderService(_registry(fleet_dir), wave_buckets=(8,))
    program = svc._predict_mixed

    @jax.jit
    def late(x):
        pause = io_callback(lambda: (time.sleep(0.05), np.float32(0))[1],
                            jax.ShapeDtypeStruct((), jnp.float32))
        return x + 0 * pause

    svc._predict_mixed = lambda X, *rest: program(late(X), *rest)
    reqs = _requests([8] * 8, [False] * 8, seed=11)
    a0, r0 = _counts()
    got = svc.serve(reqs)
    a1, r1 = _counts()
    assert (a1 - a0, r1 - r0) == (2, 6)
    ref = EncoderService(_registry(fleet_dir), wave_buckets=(8,))
    for i, (res, alone) in enumerate(zip(got, reference_serve(ref, reqs))):
        assert np.array_equal(res.predictions, alone.predictions), i


def test_concurrent_serves_never_share_a_set(fleet_dir, monkeypatch):
    """Twelve threads (more than the cores) serve through ONE service at
    once, switching often: no two builders ever fill one set, so every
    answer equals the request served alone."""
    import sys
    import threading

    from repro.serving_encoders import service as service_module

    monkeypatch.setattr(service_module, "np", _AlignedNumpy())
    svc = EncoderService(_registry(fleet_dir), wave_buckets=(8,),
                         score_slots=2)
    ref = EncoderService(_registry(fleet_dir), wave_buckets=(8,),
                         score_slots=2)
    work = [_requests([9, 14, 6], [True, False, True],
                      ["m0", "m0", "m1"], seed=200 + i) for i in range(12)]
    want = [reference_serve(ref, reqs) for reqs in work]
    got = [[] for _ in work]

    def worker(i):
        for _ in range(4):
            got[i].append(svc.serve(work[i]))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(work))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    for i, rounds in enumerate(got):
        assert len(rounds) == 4
        for results in rounds:
            for res, alone in zip(results, want[i]):
                assert np.array_equal(res.predictions, alone.predictions), i
                if alone.pearson_r is not None:
                    assert np.array_equal(res.pearson_r, alone.pearson_r), i


def _fit_bundle(root, name, p, t, seed):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((60, p)).astype(np.float32)
    Y = (X @ rng.standard_normal((p, t)).astype(np.float32)
         + rng.standard_normal((60, t)).astype(np.float32))
    path = str(root / name)
    BrainEncoder(n_folds=3).fit(X, Y).save(path)
    return path


def test_staging_pool_counts_bounds_and_spans(tmp_path):
    from repro import obs

    shapes = {"a": (12, 7), "b": (12, 9), "c": (10, 7)}
    reg = EncoderRegistry()
    for i, (name, (p, t)) in enumerate(shapes.items()):
        reg.add(name, _fit_bundle(tmp_path, name, p, t, i))
    svc = EncoderService(reg, wave_buckets=(8,), score_slots=2)
    assert svc._staging.max_keys == 2
    rng = np.random.default_rng(3)

    def req(name, rows, scored):
        p, t = shapes[name]
        return PredictRequest(
            name, rng.standard_normal((rows, p)).astype(np.float32),
            targets=(rng.standard_normal((rows, t)).astype(np.float32)
                     if scored else None))

    # One key, five waves: two sets allocated, three reuses.
    a0, r0 = _counts()
    tracer = obs.install()
    try:
        svc.serve([req("a", 13, True), req("a", 6, False),
                   req("a", 19, True)])
    finally:
        obs.uninstall()
    a1, r1 = _counts()
    assert (a1 - a0, r1 - r0) == (2, 3)
    builds = [e["attrs"] for e in tracer.events()
              if e["name"] == "serve.wave.build"]
    assert [b["reused"] for b in builds] == [0, 0, 1, 1, 1]
    # Zeroed target rows: the pad rows and the unscored request's rows.
    assert sum(b["zeroed_rows"] for b in builds) == (
        sum(b["rows"] - b["fill"] for b in builds) + 6)
    # Three shapes through a bound of two keys: the least recently used
    # is dropped ("b" by "c", then "c" by "b"), a kept one is reused.
    allocated = []
    for name in ("b", "a", "c", "a", "b"):
        svc.serve([req(name, 20, True)])
        assert len(svc._staging) <= 2
        a2, _ = _counts()
        allocated.append(a2 - a1)
        a1 = a2
    assert allocated == [2, 0, 2, 0, 2]
