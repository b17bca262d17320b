"""Per step, ms the core waits on host->device fetches of host-resident
layer weights, forward and backward: self time of their
``dynamic-slice-done`` ops (``param_fetch.py``). None where the window
holds no such fetch, as in a plan that keeps its weights in HBM."""
import param_fetch


def read(ctx):
    out = param_fetch.window_fetches(__file__, ctx)
    return None if out is None else 1e3 * out["wait_s"] / ctx.n_steps
