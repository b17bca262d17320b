"""Per step, ms of device compute self time in the step's forward pass:
instructions whose HLO ``op_name`` lies under ``jvp(model)`` and not under
``transpose(jvp(model))`` (``trace_split.py``). None where no instruction
carries the scope, as in a program that does not name its phases."""
import trace_split


def read(ctx):
    return trace_split.phase_ms(__file__, ctx, "forward")
