"""The whole fit's counted work (``work.fit_flops``) per second of fit,
as a share of the chip's bf16 peak."""
from bench import peaks, work


def read(rec):
    if not rec.fits:
        return None
    c = rec.config
    flops = work.fit_flops(c["n"], c["p"], c["t"], c["k"], len(c["lambdas"]))
    fit_s = rec.window_s / len(rec.fits)
    return 100.0 * flops / fit_s / peaks.peaks(rec.device_kind)["flops_per_s"]
