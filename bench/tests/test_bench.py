"""CPU tests of the benchmark harness: lookup by name, generators, counts,
peaks, the no-chip exit, trace reduction, and whole runs at tiny sizes
(with the control and with planted faults, which must read incorrect)."""
from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import arrivals, drivers, peaks, spec, work

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
if os.path.join(ROOT, "src") not in sys.path:   # the program under test
    sys.path.insert(0, os.path.join(ROOT, "src"))
DATA = os.path.join(os.path.dirname(__file__), "data")


# -- lookup by name ------------------------------------------------------------

DRIVER_API = ("run", "control_checks", "tiny", "faults")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.cell(cell)
    assert (c.config_name, c.traffic_name) == KINDS[cell]
    assert c.config["name"] == c.config_name
    mod = drivers.module(c.traffic["kind"])
    assert c.traffic["kind"] in mod.KINDS
    assert all(callable(getattr(mod, f)) for f in DRIVER_API)
    assert {m.name for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert c.limits


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader(metric))


def test_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.cell("no_such.cell")


def test_a_cell_added_by_files_alone(tmp_path):
    """A new configuration, traffic mix and metric are new files plus
    BENCHMARK.json entries; nothing already there is edited."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = json.loads(json.dumps(BENCH))
    b["configs"].append({"name": "tiny", "source": "x",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "x"})
    b["workloads"].append({"name": "tiny.burst", "config": "tiny",
                           "traffic": "burst", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "burst.rows", "unit": "rows",
                           "better": "higher", "source": "host_clock",
                           "layer": "admission", "moves": "serve_p95_ms",
                           "workloads": ["tiny.burst"]})
    # No workloads list: in every cell that reports serve_p95_ms.
    b["per_layer"].append({"name": "burst.depth", "unit": "rows",
                           "better": "lower", "source": "host_clock",
                           "layer": "admission", "moves": "serve_p95_ms"})
    b["end_to_end"].append({"name": "serve_p95_ms", "unit": "ms",
                            "better": "lower", "bound": 0.1,
                            "source": "host_clock",
                            "workloads": ["tiny.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "p": 8, "t": 4, "subjects": 2}))
    (tmp_path / "bench/traffic/burst.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_rows_per_s": 100, "rows": [8, 16]}))
    (tmp_path / "bench/limits/tiny.burst.json").write_text(json.dumps(
        {"pred_rel_err": 1e-5}))
    (tmp_path / "bench/metrics/burst.rows.py").write_text(
        "def read(rec):\n    return sum(r['rows'] for r in rec.requests)\n")
    c = spec.cell("tiny.burst", root=str(tmp_path))
    assert c.config["p"] == 8 and c.traffic["rate_rows_per_s"] == 100
    assert [m.name for m in c.end_to_end] == ["setup_s", "serve_p95_ms"]
    assert [m.name for m in c.per_layer] == ["burst.rows", "burst.depth"]
    assert "burst.depth" not in [m.name for m in spec.cell(
        "parcels.fit", root=str(tmp_path)).per_layer]
    rec = dataclasses.make_dataclass("R", ["requests"])([{"rows": 3},
                                                         {"rows": 4}])
    assert spec.reader("burst.rows", root=str(tmp_path))(rec) == 7


# A driver of a new kind of run, as a later change would add it: it
# echoes a seeded vector through a compiled program for the window.
ECHO_DRIVER = '''
import dataclasses
import time

import numpy as np

from bench.harness import Check

KINDS = ("echo",)


def _vector(config, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(config["n"]).astype(np.float32)


def _checks(config, limits, got, seed):
    err = float(np.max(np.abs(got - _vector(config, seed))))
    return [Check("echo_abs_err", err, limits["echo_abs_err"])]


def run(h, seed, seconds, budget):
    import jax
    import jax.numpy as jnp

    rec = h.rec
    echo = jax.jit(lambda v: v * 1.0)
    x = jnp.asarray(_vector(rec.config, seed), jnp.float32)
    echo(x).block_until_ready()
    with h.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            y = echo(x)
            rec.attempted += 1
        y.block_until_ready()
    rec.checks = _checks(rec.config, rec.limits, np.asarray(y), seed)


def control_checks(cell, seed, seconds, precision):
    lower = _vector(cell.config, seed).astype(np.float16)
    return _checks(cell.config, cell.limits, lower, seed)


def tiny(cell):
    return dataclasses.replace(cell, config=dict(cell.config, n=8))


def faults(kind):
    return {}
'''


def _add_echo_cell(tmp_path) -> dict[str, bytes]:
    """A copy of the benchmark with a cell of the new kind ``echo`` added
    as files alone; returns the copy's files as they were before."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {str(f.relative_to(tmp_path)): f.read_bytes()
              for f in (tmp_path / "bench").rglob("*") if f.is_file()}
    b = json.loads(json.dumps(BENCH))
    b["configs"].append({"name": "echo", "source": "x",
                         "file": "bench/configs/echo.json", "reduced": [],
                         "why": "x"})
    b["workloads"].append({"name": "echo.echo", "config": "echo",
                           "traffic": "echo", "chips": 1, "why": "x"})
    b["end_to_end"].append({"name": "echo_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.1,
                            "source": "host_clock",
                            "workloads": ["echo.echo"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    (tmp_path / "bench/drivers/echo.py").write_text(ECHO_DRIVER)
    (tmp_path / "bench/configs/echo.json").write_text(json.dumps(
        {"name": "echo", "n": 4096, "precision": "highest"}))
    (tmp_path / "bench/traffic/echo.json").write_text(json.dumps(
        {"kind": "echo"}))
    (tmp_path / "bench/limits/echo.echo.json").write_text(json.dumps(
        {"echo_abs_err": 0.0}))
    (tmp_path / "bench/metrics/echo_per_s.py").write_text(
        "def read(rec):\n    return rec.attempted / rec.window_s\n")
    return before


def test_a_new_kind_of_run_added_by_files_alone(tmp_path):
    """A new kind of run is a driver file, a traffic mix of its kind, a
    configuration and limits, plus BENCHMARK.json entries: the driver is
    found by its kind, and its tiny run on the CPU is correct."""
    from bench import run
    before = _add_echo_cell(tmp_path)
    root = str(tmp_path)
    mod = drivers.module("echo", root=root)
    assert mod.KINDS == ("echo",)
    c = spec.cell("echo.echo", root=root)
    assert c.root == root and c.traffic["kind"] == "echo"
    assert drivers.module("fit_back_to_back", root=root).KINDS == (
        "fit_back_to_back",)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "WORK_DIR", str(tmp_path / "work"))
        rec = execute(mod.tiny(c))
    out = run.result(c, rec, False, {"platform": "cpu", "kind": "cpu",
                                     "count": 1})
    assert out["correct"] is True and rec.attempted > 0
    assert set(out["metrics"]) == {"setup_s", "echo_per_s"}
    # Its control, a step below in precision, reads incorrect.
    assert not all(ch.ok for ch in mod.control_checks(c, BIG_SEED, 0.5,
                                                      "high"))
    # No file that was already there was edited.
    assert {k: (tmp_path / k).read_bytes() for k in before} == before


def test_an_unknown_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        drivers.module("no_such_kind")


def test_a_kind_claimed_by_two_drivers_is_an_error(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("one", "two"):
        (tmp_path / f"bench/drivers/{name}.py").write_text(
            'KINDS = ("twice",)\n')
    (tmp_path / "bench/drivers/three.py").write_text(
        'KINDS = ("closed_loop",)\n')
    with pytest.raises(spec.SpecError):
        drivers.module("twice", root=str(tmp_path))
    with pytest.raises(spec.SpecError):
        drivers.module("closed_loop", root=str(tmp_path))
    assert drivers.module("open_loop", root=str(tmp_path)).KINDS == (
        "open_loop", "closed_loop")


# -- generators ----------------------------------------------------------------

TRAFFIC = {"rate_rows_per_s": 2000, "rows": [8, 512], "zipf_a": 1.1,
           "scored_share": 0.0, "pool_rows": 1024, "clients": 4}
CONFIG = {"subjects": 3, "t": 100}
BIG_SEED = 2 ** 31 + 12345


def test_open_loop_is_determined_by_the_seed():
    a = arrivals.open_loop(TRAFFIC, CONFIG, BIG_SEED, 10.0)
    b = arrivals.open_loop(TRAFFIC, CONFIG, BIG_SEED, 10.0)
    c = arrivals.open_loop(TRAFFIC, CONFIG, BIG_SEED + 1, 10.0)
    assert a == b and a != c
    # Every seed gets the same work, in another order.
    assert sorted(r.rows for r in a) == sorted(r.rows for r in c)
    assert sorted(r.subject for r in a) == sorted(r.subject for r in c)
    assert all(8 <= r.rows < 512 and r.offset + r.rows <= 1024 for r in a)
    assert a[0].due_s == 0.0 and max(r.due_s for r in a) < 10.0
    rate = sum(r.rows for r in a) / 10.0
    assert abs(rate - 2000) / 2000 < 0.02
    counts = np.bincount([r.subject for r in a], minlength=3)
    assert counts[0] > counts[1] > counts[2] > 0       # Zipf popularity


def test_closed_loop_is_determined_by_the_seed():
    t = dict(TRAFFIC, rows=[256, 513])
    a = arrivals.closed_loop(t, CONFIG, BIG_SEED, 400)
    assert a == arrivals.closed_loop(t, CONFIG, BIG_SEED, 400)
    c = arrivals.closed_loop(t, CONFIG, 7, 400)
    assert sorted(r.rows for r in a) == sorted(r.rows for r in c)
    assert [r.client for r in a[:5]] == [0, 1, 2, 3, 0]
    assert {r.subject for r in a[:8]} == {0, 1, 2}


def test_check_sample_holds_the_largest_request():
    rows = [10, 500, 30, 40, 7, 300]
    s = arrivals.check_sample(rows, 3, BIG_SEED)
    assert len(s) == 3 and 1 in s
    assert s == arrivals.check_sample(rows, 3, BIG_SEED)


def test_data_generators_are_determined_by_the_seed():
    from bench import gen
    cfg = {"n": 64, "p": 16, "t": 8, "data": {
        "frac_responsive": 0.25, "snr_responsive": 2.0, "drift_amp": 0.3,
        "tr_seconds": 1.49}}
    X1, Y1 = gen.subject_for(BIG_SEED, cfg)
    X2, Y2 = gen.subject_for(BIG_SEED, cfg)
    X3, _ = gen.subject_for(BIG_SEED + 2 ** 32, cfg)   # high half differs
    assert np.array_equal(X1, X2) and np.array_equal(Y1, Y2)
    assert not np.array_equal(X1, X3)
    W1 = gen.encoder_for(BIG_SEED, cfg)[0]
    assert np.array_equal(W1, gen.encoder_for(BIG_SEED, cfg)[0])


# -- counts and peaks ------------------------------------------------------------

def test_fit_flops_by_hand():
    # n=10, p=2, t=3, k=2, r=2: stats 2·10·2·5 = 200; per solve
    # 8/3 + 2·4·3 = 26.667, four of them plus the refit = 133.33;
    # held-out 2·10·2·3·2 = 240.
    assert work.fit_flops(10, 2, 3, 2, 2) == pytest.approx(200 + 5 * (8 / 3 + 24) + 240)
    # The paper's parcel subject.
    assert work.fit_flops(69_202, 16_384, 444, 5, 11) == pytest.approx(
        1.4468e14, rel=1e-4)


def test_gram_and_wave_counts_by_hand():
    assert work.gram_chunk_flops(8, 2, 3) == 2 * 8 * 2 * 5
    assert work.gram_chunk_bytes(8, 2, 3) == 4 * (8 * 5 + 2 * 5)
    assert work.gram_chunk_flops(8192, 16_384, 444) == pytest.approx(
        4.5172e12, rel=1e-4)
    # rows=4, p=2, t=3: 2·4·2 + 2·4·2·3 + 2·4·3 = 16 + 48 + 24.
    assert work.wave_flops(4, 2, 3) == 88
    assert work.wave_flops(4, 2, 3, scored=True) == 88 + 8 * 4 * 3
    assert work.wave_bytes(4, 2, 3) == 4 * (6 + 8 + 10 + 12)
    assert work.wave_bytes(4, 2, 3, scored=True, slots=2) == \
        4 * (6 + 8 + 10 + 12) + 4 * (12 + 60)
    assert work.wave_flops(512, 16_384, 66_202) == pytest.approx(
        1.1107e12, rel=1e-4)


def test_peaks_table_refuses_an_unknown_chip():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.roofline_seconds(1.0, 1.0, "cpu")
    # Compute-bound and memory-bound sides of the roofline.
    assert peaks.roofline_seconds(197e12, 1.0, "TPU v5 lite") == 1.0
    assert peaks.roofline_seconds(1.0, 819e9, "TPU v5 lite") == 1.0


# -- no chip --------------------------------------------------------------------

def _bench_run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "-m", "bench.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _bench_run(["--workload", "parcels.fit", "--seed", "3",
                    "--seconds", "1"], ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A checkout with only BENCHMARK.json and bench/ has no program."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _bench_run(["--workload", "parcels.fit", "--seed", "3",
                    "--seconds", "1"], str(tmp_path))
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


# -- trace reduction --------------------------------------------------------------

def test_trace_reduction_on_a_recorded_chip_trace():
    from bench import xplane
    raw = xplane.load(os.path.join(DATA, "small_trace.xplane.pb"))
    assert list(raw["device"]) == ["/device:TPU:0"]
    t = xplane.reduce(raw)
    assert 0 < t["busy_s"] < t["window_s"]
    secs, count = xplane.op_time(t, "xty_folds_masked")
    assert secs > 0 and count == 3
    assert xplane.module_events(t, "xty_folds_masked")
    bd = t["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    idle = sum(v for _, v in bd["idle_gaps"])
    assert idle == pytest.approx(t["window_s"] - t["busy_s"], rel=1e-6)
    assert "probe.step" in dict(bd["idle_gaps"])


def test_device_time_by_scope_on_a_recorded_chip_trace():
    from bench import xplane
    raw = xplane.load(os.path.join(DATA, "small_trace.xplane.pb"))
    t = xplane.reduce(raw)
    assert "jit(xty_folds_masked)/pallas_call" in t["scopes"]
    secs, count = xplane.scope_time(t, "xty_folds_masked")
    kernel, _ = xplane.op_time(t, "xty_folds_masked")
    assert count == 8 and kernel <= secs <= t["busy_s"]
    # A scope is a whole component of the path, after jit(...).
    assert xplane.scope_time(t, "pad")[1] == 2
    assert xplane.scope_time(t, "xty_folds") == (0, 0)
    assert xplane.scope_parts("jit(a)/pjit(b)/c/jit(d)") == ["a", "b", "c", "d"]
    # Ops with no tf_op (the async copies, the slices' start and done)
    # fall under no scope: the scopes hold the rest of the ops' time.
    unscoped = [n for n, p in zip(raw["device"]["/device:TPU:0"]["XLA Ops"],
                                  raw["scopes"]["/device:TPU:0"]) if not p]
    assert unscoped and "" not in t["scopes"]
    assert sum(t["scope_count"].values()) < sum(t["op_count"].values())
    assert sum(t["scopes"].values()) < sum(t["ops"].values())


def test_idle_gaps_take_the_innermost_open_span():
    from bench import xplane
    host = [("outer", 0.0, 10.0, 0), ("inner", 2.0, 2.0, 1),
            ("other_thread_late", 9.5, 1.0, 0)]
    assert xplane._open_at([1.0, 3.0, 9.8, 11.0], host) == [
        "outer", "inner", "other_thread_late", "no host span"]


# -- whole runs at tiny sizes, on the CPU --------------------------------------------

# Every cell of BENCHMARK.json, and the open-loop mix kept for the predict
# cell (PERF.md, Open questions, item 1).
KINDS = {**{w["name"]: (w["config"], w["traffic"])
            for w in BENCH["workloads"]},
         "wholebrain_q4.predict": ("wholebrain_q4", "predict")}
CELLS = list(KINDS)


def driver_of(cell: spec.Cell):
    return drivers.module(cell.traffic["kind"], cell.root)


def tiny(cell_name: str) -> spec.Cell:
    c = spec.assemble(cell_name, *KINDS[cell_name])
    return driver_of(c).tiny(c)


# One byte of device memory: at a tiny size each cell still takes the
# path its full size takes where the data outgrows the chip (a fit
# streams its store).
TINY_BUDGET = 1


def execute(cell: spec.Cell, seed: int = BIG_SEED, seconds: float = 0.5,
            trace: bool = False):
    from bench import run
    return run.execute(cell, seed, seconds, trace, device_kind="TPU v5 lite",
                       budget=TINY_BUDGET, t_start=time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_is_correct(cell):
    from bench import run
    c = tiny(cell)
    rec = execute(c)
    assert rec.attempted > 0 and rec.failed == 0
    assert rec.compiles_in_window == 0
    assert [ch for ch in rec.checks if not ch.ok] == []
    out = run.result(c, rec, False, {"platform": "cpu", "kind": "cpu",
                                     "count": 1})
    assert out["correct"] is True
    assert set(out["metrics"]) == {m.name for m in c.end_to_end}
    assert list(out)[-1] == "checks"
    assert all(math.isfinite(m["value"]) for m in out["metrics"].values())


# -- the control and planted faults must read incorrect ----------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_incorrect(cell):
    """The reference one precision step below the configuration's, in the
    program's place, fails at least one of the cell's committed limits."""
    from bench import readings
    checks = readings.control_checks(tiny(cell), BIG_SEED, 0.5)
    assert [c for c in checks if not c.ok], checks


def _faults(cell_name: str) -> dict:
    c = spec.assemble(cell_name, *KINDS[cell_name])
    return driver_of(c).faults(c.traffic["kind"])


# The faults each cell's driver plants; no cell here exchanges anything
# between chips.
FAULTS = [(cell, fault) for cell in CELLS for fault in _faults(cell)]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_planted_fault_reads_incorrect(cell, fault, monkeypatch):
    target, attr, fn = _faults(cell)[fault]
    monkeypatch.setattr(target, attr, fn)
    rec = execute(tiny(cell))
    assert rec.attempted > 0
    assert [c for c in rec.checks if not c.ok], rec.checks
