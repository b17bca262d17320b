"""Per step, ms of device compute self time in the optimizer update:
instructions whose HLO ``op_name`` lies under ``optimizer``
(``trace_split.py``). The host copies of offloaded optimizer state are not
compute: ``offload.exposed_ms`` holds their waits. None where no
instruction carries the scope."""
import trace_split


def read(ctx):
    return trace_split.phase_ms(__file__, ctx, "optimizer")
