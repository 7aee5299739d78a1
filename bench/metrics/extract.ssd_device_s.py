"""Device seconds per fit in the ops under the ``ssd`` scope: the Mamba-2
layers' chunked SSD, from the trace."""
from bench import xplane


def read(rec):
    if rec.trace is None or not rec.fits:
        return None
    secs, count = xplane.scope_time(rec.trace, "ssd")
    if not count:
        return None
    return secs / rec.trace["chips"] / len(rec.fits)
