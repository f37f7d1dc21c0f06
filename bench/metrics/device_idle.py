"""Share of the traced window in which no operation ran on the device."""
from bench import records


def compute(rec):
    return records.device_idle_pct(rec)
