"""Device seconds per fit in the ops under the ``cv_solve`` scope (on the
TPU the per-λ Cholesky solves; the refit, the same solve, may run as
the same program), from the trace."""
from bench import xplane


def read(rec):
    if rec.trace is None or not rec.fits:
        return None
    secs, count = xplane.scope_time(rec.trace, "cv_solve")
    if not count:
        return None
    return secs / rec.trace["chips"] / len(rec.fits)
