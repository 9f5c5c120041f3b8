"""Whole-step share of the chip's bf16 peak, from the trace: executions
of the step's program inside the traced window x clouds per step x the
published network's dense FLOPs per cloud / the traced window's seconds
/ peak FLOP/s."""
from bench import families
from bench.metrics._device import step_runs


def read(ctx):
    runs, t = step_runs(ctx), ctx["trace"]
    if not runs or t["window_s"] <= 0:
        return None
    cfg = ctx["config"]
    work = runs * ctx["traffic"]["batch"] \
        * families.of(cfg).model_flops_per_cloud(cfg)
    return 100.0 * work / t["window_s"] / ctx["peaks"]["flops_per_s"]
