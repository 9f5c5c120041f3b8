"""Share of the padded bucket rows that carried no real point, over the
batches dispatched in the window (the server's own counters)."""


def read(ctx):
    return ctx.get("padding_waste_pct")
