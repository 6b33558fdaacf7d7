"""Peak device memory in use over the run so far, fullest chip, in GB."""


def compute(ctx):
    return ctx["memory_peak_bytes"] / 1e9
