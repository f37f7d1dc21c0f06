"""Host-clock wall of the window's ``prefill`` steps per 1,000 prompt
tokens they prefilled (engine counter ``prefill_tokens``)."""
from bench import records


def compute(rec):
    spans = records.window_spans(rec, "prefill")
    toks = records.stats_delta(rec, "prefill_tokens")
    if not spans or toks <= 0:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / (toks / 1e3)
