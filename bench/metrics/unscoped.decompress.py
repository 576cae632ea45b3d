"""Share of the traced decompress window's device busy time in ops under
no decompress stage scope, so seen by no stage metric of the cell."""

STAGES = ("toposzp.stage_decode", "toposzp.stage_restore",
          "szp.stage_restore")


def read(ctx):
    busy = ctx.red.busy_s
    if ctx.operation != "decompress" or busy <= 0:
        return None
    return 100.0 * (1.0 - ctx.red.scope_s(*STAGES) / busy)
