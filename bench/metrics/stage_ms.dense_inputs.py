"""Device time of the FC kernels' operands (``pcn.dense_inputs``: gathers
and pads around ``gather_mlp`` and ``hub_reuse``, the fallback merge,
the post-pool activation), in ms per cloud answered in the window.
Each instant counts to the innermost operation running; a loop's time
outside its body counts to the scope around it."""
from bench.metrics._stages import stage_ms


def read(ctx):
    return stage_ms(ctx, "dense_inputs")
