"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing here runs on a device: ``jax.experimental.topologies`` describes a
v5e chip that is not attached, and each kernel is lowered and compiled for
it at the paper's widths (p = 16,384 features, t = 444 parcels, the
11-point λ grid).  Mosaic refuses what interpret mode accepts — block
shapes off the (8, 128) tiling, too much VMEM — so these compiles guard
the chip path from a CPU-only test run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

P, T, R = 16_384, 444, 11          # paper feature width, parcels, λ grid
K = 5                              # folds
V5E_BYTES_LIMIT = 16_909_336_064    # memory_stats()['bytes_limit'], v5e


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel emitted"


def test_xty_folds_masked_compiles(one_chip):
    from repro.kernels import gram
    m, s = 2048, 2
    compiled = gram.xty_folds_masked.lower(
        _spec((m, P), one_chip), _spec((m, P + T), one_chip),
        _spec((m, s), one_chip), interpret=False).compile()
    _assert_kernel(compiled)


def test_solve_lambda_grid_compiles(one_chip):
    from repro.kernels import ridge_solve
    compiled = ridge_solve.solve_lambda_grid.lower(
        _spec((P, P), one_chip), _spec((P,), one_chip),
        _spec((P, T), one_chip), _spec((R,), one_chip),
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("name", ["xty", "pearson_r"])
def test_streaming_kernels_compile(one_chip, name):
    from repro.kernels import gram, pearsonr
    if name == "xty":
        lowered = gram.xty.lower(_spec((2048, P), one_chip),
                                 _spec((2048, T), one_chip), interpret=False)
    else:
        lowered = pearsonr.pearson_r.lower(_spec((4096, T), one_chip),
                                           _spec((4096, T), one_chip),
                                           interpret=False)
    _assert_kernel(lowered.compile())


def test_ridge_solve_code_does_not_grow_with_p(one_chip):
    """The TPU's per-λ factorisation (``core.spd``) compiles at p = 16,384
    to a fixed-size loop; XLA's own Cholesky solve there is 417 MB of
    code and takes minutes to compile."""
    from repro.core import spd
    compiled = spd.ridge_solve.lower(
        _spec((P, P), one_chip), _spec((P, T), one_chip),
        _spec((), one_chip)).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 64e6


def test_chunk_update_fits_one_chip(one_chip, monkeypatch):
    """The streamed fit's whole chunk update at p = 16,384 — fused kernel,
    Chan merge and slot adds into the donated (k, p, p+t) statistics —
    compiles for one chip and plans fewer bytes than it may allocate."""
    from repro.core import foldstats
    from repro.kernels import ops
    # The backend here is the CPU, which would interpret the kernel.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    m, s = 2048, 2
    stats = foldstats.FoldStats(
        G=_spec((K, P, P), one_chip), C=_spec((K, P, T), one_chip),
        xsum=_spec((K, P), one_chip), ysum=_spec((K, T), one_chip),
        ysq=_spec((K, T), one_chip), count=_spec((K,), one_chip))
    upd = foldstats._FixedShapeUpdate()
    compiled = upd._fn.lower(
        stats, _spec((m, P), one_chip), _spec((m, T), one_chip),
        _spec((m, s), one_chip), _spec((s,), one_chip, jnp.int32),
        use_pallas=True).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # The statistics are updated in place: the output aliases them, so
    # the plan holds one copy of (k, p, p+t), not two.
    assert mem.alias_size_in_bytes >= K * P * (P + T) * 4
    assert planned < V5E_BYTES_LIMIT, planned


def test_cv_scoring_program_fits_one_chip(one_chip):
    """The per-λ path's held-out scoring, one program at p = 16,384 over
    the whole fold statistics, plans fewer bytes than one chip may
    allocate, and its ops keep their scope for the device trace."""
    from repro.core import foldstats, ridge
    stats = foldstats.FoldStats(
        G=_spec((K, P, P), one_chip), C=_spec((K, P, T), one_chip),
        xsum=_spec((K, P), one_chip), ysum=_spec((K, T), one_chip),
        ysq=_spec((K, T), one_chip), count=_spec((K,), one_chip))
    compiled = ridge._cv_score.lower(
        stats, _spec((), one_chip, jnp.int32), _spec((R, P, T), one_chip),
        "r2").compile()
    mem = compiled.memory_analysis()
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert planned < V5E_BYTES_LIMIT, planned
    assert "/cv_score/" in compiled.as_text()
