"""Operations and bytes each measured piece of work needs, from shapes.

These counts are what the algorithm needs, never what one implementation
happens to do, so a later change of algorithm cannot move them: a masked
factorisation that does six times a dense one's work reads as a lower
share, not as more work.  FLOP counts a multiply and an add as two.
"""
from __future__ import annotations

F32 = 4


def cholesky_solve_flops(p: int, t: int) -> float:
    """One dense Cholesky factorisation of a p×p SPD matrix (p³/3) and
    the two triangular sweeps over t right-hand sides (2·p²·t)."""
    return p ** 3 / 3 + 2 * p ** 2 * t


def fit_flops(n: int, p: int, t: int, k: int, r: int) -> float:
    """A cross-validated ridge fit of one subject.

    * one pass of statistics over the rows, ``[G | C] = Xᵀ[X | Y]``:
      2·n·p·(p+t);
    * per fold and λ, one dense Cholesky solve: k·r·(p³/3 + 2·p²·t);
    * held-out prediction for every fold and λ: 2·n·p·t·r in all
      (each row is held out once);
    * the refit at the chosen λ: p³/3 + 2·p²·t.

    At n = 69,202, p = 16,384, t = 444, k = 5, r = 11 this is 1.447e14.
    """
    return (2.0 * n * p * (p + t)
            + k * r * cholesky_solve_flops(p, t)
            + 2.0 * n * p * t * r
            + cholesky_solve_flops(p, t))


def gram_chunk_flops(m: int, p: int, t: int) -> float:
    """One chunk's ``[G | C]`` contribution, one pass: 2·m·p·(p+t),
    whatever number of fold slots the kernel sweeps."""
    return 2.0 * m * p * (p + t)


def gram_chunk_bytes(m: int, p: int, t: int) -> float:
    """One chunk read once (m·(p+t) f32) and one (p, p+t) f32 slab
    written."""
    return F32 * (m * (p + t) + p * (p + t))


def wave_flops(rows: int, p: int, t: int, scored: bool = False) -> float:
    """A serving wave: standardise the rows (2·rows·p), ``X @ W``
    (2·rows·p·t), de-standardise (2·rows·t); scored waves add the five
    Pearson moment sums (8·rows·t: three products, five adds)."""
    f = 2.0 * rows * p + 2.0 * rows * p * t + 2.0 * rows * t
    if scored:
        f += 8.0 * rows * t
    return f


def wave_bytes(rows: int, p: int, t: int, scored: bool = False,
               slots: int = 0) -> float:
    """A serving wave reads W (p·t), the rows (rows·p) and the μ/σ
    vectors, and writes the predictions (rows·t); scored waves also read
    the targets (rows·t) and read and write the per-slot sums
    (slots·5·t each way)."""
    b = F32 * (p * t + rows * p + 2 * (p + t) + rows * t)
    if scored:
        b += F32 * (rows * t + 2 * slots * 5 * t)
    return b
