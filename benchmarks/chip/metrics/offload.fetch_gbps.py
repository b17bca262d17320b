"""GB/s of host->device copies of offloaded state: bytes of every fetch in
the window (from the copies' shapes) over the union of their in-flight
intervals on the ``Async XLA Ops`` line (``trace_split.py``). The link's
rate while fetches are in flight; None where the window holds none."""
import trace_split


def read(ctx):
    return trace_split.link_gbps(__file__, ctx, "fetch")
