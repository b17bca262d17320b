"""Per step, the device time the core spends waiting on host<->device
copies of offloaded state, with nothing else running (``trace_reduce.py``
class ``host_transfer``, self time). None where the trace holds no such
copy."""


def read(ctx):
    if "host_transfer" not in ctx.trace["class_s"]:
        return None
    return 1e3 * ctx.trace["class_s"]["host_transfer"] / ctx.n_steps
