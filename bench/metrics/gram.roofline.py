"""Share of its roofline reached by the fused ``[G|C]`` chunk kernel
(``kernels/gram.xty_folds_masked``): one pass's work and bytes per chunk
over the kernel's device time in the trace."""
from bench import peaks, work, xplane

KERNEL = ("xty_masked", "xty_folds_masked")


def read(rec):
    if rec.trace is None:
        return None
    secs, _ = xplane.op_time(rec.trace, *KERNEL)
    if not secs:
        return None
    c = rec.config
    calls = sum(f["chunks"] for f in rec.fits)
    m = rec.fits[0]["chunk_rows"]
    least = peaks.roofline_seconds(work.gram_chunk_flops(m, c["p"], c["t"]),
                                   work.gram_chunk_bytes(m, c["p"], c["t"]),
                                   rec.device_kind)
    return 100.0 * calls * least / (secs / rec.trace["chips"])
