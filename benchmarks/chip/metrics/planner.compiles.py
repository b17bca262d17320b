"""Compiles ``fit_plan`` made before the chip's compiler accepted a plan:
the refused ones (``FitResult.misses``) and the accepted one. Each refusal
is a memory-model miss paid again at every start, so it moves setup_s."""


def read(ctx):
    return ctx.compiles
