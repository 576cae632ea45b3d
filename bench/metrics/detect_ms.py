"""Device ms per field of pass 1's CD + RP rank sort (``toposzp.stage_detect``)."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_detect")
