"""Share of the traced window in which the device was idle while the
server's own host work had a span open on some thread (``serve.fire``,
``serve.pad``, ``serve.readback``, ``serve.complete``): the idle that the
server causes.  The rest of ``device_idle_pct.serve`` is idle for want
of requests."""
from bench.metrics._stages import idle_in_host_pct


def read(ctx):
    return idle_in_host_pct(ctx)
