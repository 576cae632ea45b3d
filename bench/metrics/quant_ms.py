"""Device ms per field of pass 1's QZ + LZ (and TopoSZp's rank metadata):
ops under ``toposzp.stage_quant`` or ``szp.stage_quant``."""


def read(ctx):
    return ctx.ms_per_field("toposzp.stage_quant", "szp.stage_quant")
