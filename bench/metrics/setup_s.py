"""Process start to the first timed round: build, weights, warm-up call."""


def compute(ctx):
    return ctx["setup_s"]
