"""Device ms per field of SZp's decompress: BE^ unpack, LZ^ + B^ and QZ^
dequant (``szp.stage_restore``; plain SZp restores nothing)."""


def read(ctx):
    return ctx.ms_per_field("szp.stage_restore")
