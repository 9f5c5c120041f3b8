"""Device time of operations outside every ``pcn.*`` scope, in ms per cloud
answered in the window.
None where no operation of the trace carries a stage."""
from bench.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "unscoped")
