"""Median over every request due in the window: scheduled arrival to
first token (a request with none by the end counts as end - due)."""
from bench import records


def compute(rec):
    return records.percentile(records.ttft_samples(rec), 50)
