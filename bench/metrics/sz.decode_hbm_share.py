"""SZ decode: (raw bytes + stream bytes) over peak HBM bandwidth times the
device busy time inside the decompress spans (%)."""

from bench.metrics._share import hbm_percent


def read(ctx):
    return hbm_percent(ctx, "tpu-sz", "decompress")
