"""Device time of DGCNN's embedding (``pcn.embed``: the per-point layer
on the concatenated EdgeConv outputs, its max pool and activation), in
ms per cloud answered in the window.
Each instant counts to the innermost operation running; a loop's time
outside its body counts to the scope around it."""
from bench.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "embed")
