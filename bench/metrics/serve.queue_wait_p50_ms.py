"""Median queue wait (the server's own record: dispatch - arrival) of
the requests served in the window."""
import statistics


def read(ctx):
    waits = ctx.get("queue_wait_ms")
    return statistics.median(waits) if waits else None
