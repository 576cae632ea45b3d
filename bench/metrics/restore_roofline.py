"""Share of peak HBM bandwidth in the restore stage: the stage's logical
bytes (``work.restore_bytes``) over its device time."""
from bench import work


def read(ctx):
    ms = ctx.ms_per_field("toposzp.stage_restore")
    if ms is None:
        return None
    return work.hbm_share(work.restore_bytes(ctx.n_points), ms / 1000.0,
                          ctx.device_kind)
