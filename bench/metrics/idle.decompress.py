"""Share of the traced decompress window in which no op ran on the device."""


def read(ctx):
    return ctx.idle_pct() if ctx.operation == "decompress" else None
