"""Device ms per field of pass 1's RP rank sort alone
(``toposzp.stage_rp``, nested in ``toposzp.stage_detect``)."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_rp")
