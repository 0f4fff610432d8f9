"""Share of its HBM roofline that the window-tier Pallas kernel
(``skipper_pipeline_kernel``) reaches in the traced window."""
from bench.metrics import tier_bytes


def read(run):
    return tier_bytes.roofline_share(run, "skipper_pipeline_kernel",
                                     tier_bytes.window_tier(run.schedule))
