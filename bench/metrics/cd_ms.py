"""Device ms per field of pass 1's critical-point detection alone
(``toposzp.stage_cd``, nested in ``toposzp.stage_detect``): the 4- or
6-neighbour CD kernel and the shifted views it reads."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_cd")
