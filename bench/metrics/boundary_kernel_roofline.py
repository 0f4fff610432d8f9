"""Share of its HBM roofline that the boundary Pallas kernel
(``skipper_boundary_kernel``, summed over its chunked calls) reaches in the
traced window."""
from bench.metrics import tier_bytes


def read(run):
    return tier_bytes.roofline_share(run, "skipper_boundary_kernel",
                                     tier_bytes.boundary_tier(run.schedule))
