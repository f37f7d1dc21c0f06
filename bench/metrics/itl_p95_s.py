"""95th percentile over every gap between consecutive tokens of the
requests due in the window."""
from bench import records


def compute(rec):
    return records.percentile(records.itl_samples(rec), 95)
