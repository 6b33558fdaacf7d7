"""Blocking device-to-host transfers in ``run_fused``'s metric fetch per
round it ran, from the program's own counters (``repro.obs``); every call of
a cell is alike, so the ratio does not depend on how many calls ran. Absent
where the program keeps no such counters."""


def compute(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    counts = obs.counters()
    rounds = counts.get("trainer.rounds")
    if not rounds:
        return None
    return counts.get("trainer.d2h_transfers", 0) / rounds
