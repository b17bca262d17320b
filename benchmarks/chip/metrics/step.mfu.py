"""Model FLOP/s utilisation of the whole step, in %: training operations
per token (``flops.py``: forward and backward, recomputation not counted)
times tokens/s of the traced window, over the chips' bf16 peak
(``peaks.json``). It bounds what any kernel's gain can add to tokens_per_s."""
from flops import train_flops_per_token


def read(ctx):
    if ctx.peaks is None:
        return None
    fpt = train_flops_per_token(ctx.cfg, ctx.traffic["seq_len"])
    return 100.0 * ctx.tokens_per_s * fpt / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
