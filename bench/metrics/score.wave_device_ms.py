"""Mean device time of one execution of the wave program
(``_predict_mixed``), from the trace."""
from bench.readers import wave_device_ms


def read(rec):
    return wave_device_ms(rec)
