"""The paged decode kernel's share of its roofline: least time its calls
need at the chip's peaks over its device time in the trace."""
from bench import records


def compute(rec):
    return records.kernel_roofline_pct(rec, "decode_step_paged:tpu_custom_call")
