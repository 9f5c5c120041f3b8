"""Device time of the reuse operands (``pcn.reuse_inputs``: hub pool
inputs, delta compensation MLP), in ms per cloud answered in the window.
Each instant counts to the innermost operation running; a loop's time
outside its body counts to the scope around it."""
from bench.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "reuse_inputs")
