"""Share of the traced compress window in which no op ran on the device."""


def read(ctx):
    return ctx.idle_pct() if ctx.operation == "compress" else None
