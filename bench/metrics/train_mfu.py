"""A round's required operations over the chips' peak in the round's time.

Required: 6·P·B·steps·N for the local forward and backward passes plus
2·nnz(W)·P for the mix (``counts.py``); evals are left out and dead nodes
count as training, so this is a lower bound on the work done.
"""


def compute(ctx):
    flops = ctx["counts"].get("flops_per_round")
    if not flops:
        return None
    round_s = ctx["window_s"] / ctx["rounds"]
    return 100.0 * flops / (round_s * ctx["chips"] * ctx["peaks"]["flops_per_s"])
