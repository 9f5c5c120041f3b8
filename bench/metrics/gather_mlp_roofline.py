"""Roofline share of the dense FC kernel ``gather_mlp``."""
from bench.metrics._device import kernel_roofline_pct


def read(ctx):
    return kernel_roofline_pct(ctx, "gather_mlp")
