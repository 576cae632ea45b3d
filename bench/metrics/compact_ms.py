"""Device ms per field of the BE compaction kernel, found by its kernel
name ``compact_local_blocks`` (the Pallas call's scope)."""


def read(ctx):
    return ctx.ms_per_field("compact_local_blocks")
