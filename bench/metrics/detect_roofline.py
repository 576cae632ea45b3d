"""Share of peak HBM bandwidth in pass 1's CD + RP stage: the stage's
logical bytes (``work.detect_bytes``) over its device time."""
from bench import work


def read(ctx):
    ms = ctx.ms_per_field("toposzp.stage_detect")
    if ms is None:
        return None
    return work.hbm_share(work.detect_bytes(ctx.n_points), ms / 1000.0,
                          ctx.device_kind)
