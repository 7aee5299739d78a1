"""Share of its roofline reached by the wave program: 2·rows·p·t FLOP
and the W, X and output bytes per wave, over its device time."""
from bench.readers import wave_roofline_percent


def read(rec):
    return wave_roofline_percent(rec, scored=False)
