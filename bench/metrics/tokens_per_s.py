"""Prompt tokens prefilled plus tokens generated inside the window, over
the window."""
from bench import records


def compute(rec):
    w = rec["window"]
    return records.window_tokens(rec) / (w["w1"] - w["w0"])
