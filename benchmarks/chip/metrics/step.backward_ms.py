"""Per step, ms of device compute self time in the step's backward pass,
recomputation included: instructions whose HLO ``op_name`` lies under
``transpose(jvp(model))`` (``trace_split.py``). None where no instruction
carries the scope."""
import trace_split


def read(ctx):
    return trace_split.phase_ms(__file__, ctx, "backward")
