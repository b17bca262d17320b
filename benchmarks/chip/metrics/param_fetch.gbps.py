"""GB/s of host->device fetches of host-resident layer weights: their
bytes in the window over the union of their in-flight intervals
(``param_fetch.py``), to set against the host link's rate. None where the
window holds no such fetch."""
import param_fetch


def read(ctx):
    out = param_fetch.window_fetches(__file__, ctx)
    return None if out is None else out["bytes"] / out["inflight_s"] / 1e9
