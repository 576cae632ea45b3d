"""Share of peak HBM bandwidth in pass 1's critical-point detection: the
stage's logical bytes (``cd_bytes``) over its device time under
``toposzp.stage_cd``."""
from bench import work


def cd_bytes(n_points: int) -> float:
    """CD of one field, whatever its rank: read the f32 field, write the
    2-bit label map (4.25 B per point)."""
    return 4.0 * n_points + n_points / 4.0


def read(ctx):
    ms = ctx.ms_per_field("toposzp.stage_cd")
    if ms is None:
        return None
    return work.hbm_share(cd_bytes(ctx.n_points), ms / 1000.0,
                          ctx.device_kind)
