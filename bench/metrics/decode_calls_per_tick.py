"""Decode calls per engine tick over the window: one call per live
cohort, so mixed prompt lengths split the batch (engine counters)."""
from bench import records


def compute(rec):
    ticks = records.stats_delta(rec, "decode_ticks")
    if ticks <= 0:
        return None
    return records.stats_delta(rec, "decode_steps") / ticks
