"""Stream bytes over the host-clock time of the fetch spans (GB/s)."""

from bench.metrics._share import link_gbps


def read(ctx):
    return link_gbps(ctx, "fetch")
