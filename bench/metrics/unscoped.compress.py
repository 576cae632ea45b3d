"""Share of the traced compress window's device busy time in ops under no
compress stage scope, so seen by no stage metric of the cell."""

STAGES = ("toposzp.stage_detect", "toposzp.stage_quant",
          "toposzp.stage_pack", "szp.stage_quant", "szp.stage_pack",
          "compact_local_blocks")


def read(ctx):
    busy = ctx.red.busy_s
    if ctx.operation != "compress" or busy <= 0:
        return None
    return 100.0 * (1.0 - ctx.red.scope_s(*STAGES) / busy)
