"""Share of its roofline reached by the scored wave program: the
prediction work plus the Pearson moments, and the target and sum bytes."""
from bench.readers import wave_roofline_percent


def read(rec):
    return wave_roofline_percent(rec, scored=True)
