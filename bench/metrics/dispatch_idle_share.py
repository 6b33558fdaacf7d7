"""Share of the traced window in which the chip ran no op while the host was
inside ``run_fused``'s ``trainer.dispatch`` span (one fused chunk enqueued),
mean over the chips; absent where the program has no ``trainer.`` spans."""

from scopes import span_idle_share


def compute(ctx):
    return span_idle_share(ctx["trace"], "trainer.dispatch")
