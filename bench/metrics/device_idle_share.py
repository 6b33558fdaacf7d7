"""Share of the traced window in which no op ran, mean over the chips."""

from devtrace import busy_ns


def compute(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"]:
        return None
    lo, hi = tr["window"]
    idle = [1.0 - busy_ns(tr, d) / (hi - lo) for d in tr["devices"]]
    return 100.0 * sum(idle) / len(idle)
