"""Mean host-clock wall of the window's steps that returned ``decode``:
device calls plus the host work of one tick."""
from bench import records


def compute(rec):
    spans = records.window_spans(rec, "decode")
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(spans)
