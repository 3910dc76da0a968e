"""Share of the compress spans in which no operation ran on the device (%)."""

from bench.metrics._share import idle_percent


def read(ctx):
    return idle_percent(ctx, "compress")
