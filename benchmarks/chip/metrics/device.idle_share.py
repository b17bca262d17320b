"""Share of the traced window, in %, in which no operation ran on the
device (averaged over the chips; ``trace_reduce.py``)."""


def read(ctx):
    return 100.0 * ctx.trace["idle_share"]
