"""Device ms per field of CP^ + RP^ + RS^ + FP/FT suppression
(``toposzp.stage_restore``)."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_restore")
