"""Device ms per field of BE^ -> LZ^+B^ -> QZ^ -> MD^
(``toposzp.stage_decode``)."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_decode")
