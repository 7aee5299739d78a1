"""The whole cell's counted work — one extraction (``work_backbone``) and
one fit (``work.fit_flops``) — per second of fit, as a share of the chip's
bf16 peak."""
from bench import peaks, work, work_backbone


def read(rec):
    if not rec.fits or "run_tokens" not in rec.fits[0]:
        return None
    c = rec.config
    flops = (work_backbone.backbone_flops(c, rec.fits[0]["run_tokens"])
             + work.fit_flops(c["n"], c["p"], c["t"], c["k"],
                              len(c["lambdas"])))
    fit_s = rec.window_s / len(rec.fits)
    return 100.0 * flops / fit_s / peaks.peaks(rec.device_kind)["flops_per_s"]
