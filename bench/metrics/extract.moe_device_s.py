"""Device seconds per fit in the routed experts, from the trace: the ops
under the ``moe_routed`` scope (routing, sort, gathers, scatter-add) and
the grouped expert matmuls (``ragged-dot`` ops, which carry no scope
path)."""
from bench import work_backbone, xplane


def read(rec):
    if rec.trace is None or not rec.fits:
        return None
    secs, count = xplane.scope_time(rec.trace, "moe_routed")
    if not count:
        return None
    secs += work_backbone.grouped_matmul_seconds(rec.trace)
    return secs / rec.trace["chips"] / len(rec.fits)
