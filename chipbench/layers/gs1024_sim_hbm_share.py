"""`sim_hbm_share` read in `gs1024-4rank-insitu`: one read and one write of
u and v, f32, PER FRAME (`arith.sim_floor_bytes_per_frame`: 17.18 GB over
the four ranks at 1024^3, 4.29 GB a device) over the sim program's device
time and the published HBM bandwidth; cannot pass 100 %. The accepted
reader's own code, loaded from its file (see `gs1024_sim_device_ms`)."""

import os

from chipbench import harness

NAME = "gs1024_sim_hbm_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["gs1024-4rank-insitu"]
_ACCEPTED = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "sim_hbm_share.py"))


def read(ctx):
    return _ACCEPTED.read(ctx)
