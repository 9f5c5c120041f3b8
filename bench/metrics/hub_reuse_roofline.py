"""Roofline share of the hub-cache FC kernel ``hub_reuse``."""
from bench.metrics._device import kernel_roofline_pct


def read(ctx):
    return kernel_roofline_pct(ctx, "hub_reuse")
