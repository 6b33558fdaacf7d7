"""A round's least HBM traffic per chip over the chip's peak bandwidth.

Least traffic: each node's parameters and momentum read once and written
once, plus on several chips the widest shard's remote halo rows read once
(``counts.py``).
"""


def compute(ctx):
    nbytes = ctx["counts"].get("bytes_per_round_per_chip")
    if not nbytes:
        return None
    round_s = ctx["window_s"] / ctx["rounds"]
    return 100.0 * nbytes / (round_s * ctx["peaks"]["hbm_bytes_per_s"])
