"""Device ms per field of pass 2's BE pack and compaction: ops under
``toposzp.stage_pack`` or ``szp.stage_pack``."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_pack", "szp.stage_pack")
