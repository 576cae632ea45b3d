"""Device ms per field of MD^, the metadata part of decode: label unpack,
rank-stream decode and the CP-first gather (``toposzp.stage_decode_md``,
nested in ``toposzp.stage_decode``)."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_decode_md")
