"""Share of the traced window in which no operation ran on the device
(batch cells): 1 - union of device-op intervals / window."""
from bench.metrics._device import idle_pct


def read(ctx):
    return idle_pct(ctx)
