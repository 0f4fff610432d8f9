"""Scheduled slots per valid edge: the dense rows' slots plus the padded
global-tier slots, over the valid edges (the program's exact counts)."""


def read(run):
    s = run.schedule
    slots = (s["num_rows"] * s["tiles_per_window"] * s["tile_size"]
             + s["num_boundary_padded"])
    return slots / s["num_valid"]
