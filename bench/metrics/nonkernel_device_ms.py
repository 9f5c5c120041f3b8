"""Device busy time outside the two Pallas kernels (the structure
stage and the jnp layers), in ms per cloud answered in the window."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["clouds"]:
        return None
    return 1e3 * t["nonkernel_s"] / ctx["clouds"]
