"""Window wall time over the rounds completed in it, evals included."""


def compute(ctx):
    return ctx["window_s"] / ctx["rounds"] * 1e3
