"""Model FLOPs of the tokens processed in the traced window (active
parameters, no padded or masked work) over the window at the chip's
bf16 peak."""
from bench import records


def compute(rec):
    return records.step_mfu_pct(rec)
