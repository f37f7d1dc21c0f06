"""Process start to window start: weights, compiles or cache loads,
warm-up and lead-in."""


def compute(rec):
    return rec["setup_s"]
