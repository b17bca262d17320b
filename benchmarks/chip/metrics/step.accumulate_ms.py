"""Per step, ms of device compute self time in the microbatch loop's own
work: the gradient buffers and each microbatch's fold into them,
instructions whose HLO ``op_name`` lies under ``accumulate`` and under no
``jvp(model)`` (``trace_split.py``). None where no instruction carries the
scope."""
import trace_split


def read(ctx):
    return trace_split.phase_ms(__file__, ctx, "accumulate")
