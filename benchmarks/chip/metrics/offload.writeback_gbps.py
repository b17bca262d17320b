"""GB/s of device->host copies of offloaded state (copy results in host
memory ``S(5)``): bytes over the union of their in-flight intervals
(``trace_split.py``). None where the window holds none."""
import trace_split


def read(ctx):
    return trace_split.link_gbps(__file__, ctx, "writeback")
