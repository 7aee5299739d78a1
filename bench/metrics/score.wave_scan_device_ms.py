"""Device ms per wave in the ops under the ``wave_scan`` scope (the wave
program's serial scan of the Pearson sums), from the trace."""
from bench import xplane


def read(rec):
    if rec.trace is None:
        return None
    secs, count = xplane.scope_time(rec.trace, "wave_scan")
    waves = xplane.module_events(rec.trace, "_predict_mixed")
    if not count or not waves:
        return None
    return 1e3 * secs / len(waves)
