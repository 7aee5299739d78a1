"""Real rows' prediction work over the time spent in flushes, as a
share of the chip's bf16 peak: the whole serving step."""
from bench.readers import flush_mfu_percent


def read(rec):
    return flush_mfu_percent(rec)
