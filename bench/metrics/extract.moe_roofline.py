"""Share of its roofline reached by the routed experts' grouped matmuls:
2·3·d·f FLOP per routed pair, and per call (one layer of one batch) the
held experts' weights read once plus each pair's row in and out in bf16
(``work_backbone``), over the device time of the ``ragged-dot`` ops in the
trace (they carry no scope path of their own)."""
from bench import peaks, work_backbone


def read(rec):
    if rec.trace is None or not rec.fits or "pairs" not in rec.fits[0]:
        return None
    secs = work_backbone.grouped_matmul_seconds(rec.trace)
    if not secs:
        return None
    c = rec.config
    calls = sum(f["moe_calls"] for f in rec.fits)
    pairs = sum(f["pairs"] for f in rec.fits)
    flops = pairs * work_backbone.pair_flops(c)
    nbytes = work_backbone.moe_call_bytes(c, pairs / calls) * calls
    least = peaks.roofline_seconds(flops, nbytes, rec.device_kind)
    return 100.0 * least / (secs / rec.trace["chips"])
