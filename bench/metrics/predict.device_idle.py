"""Share of the traced window in which no operation ran on the device."""
from bench.readers import idle_percent


def read(rec):
    return idle_percent(rec)
