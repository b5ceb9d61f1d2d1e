"""`composite_hbm_share` read in `gs1024-4rank-insitu`: per device one read
of the 64 supersegments that arrive for its 1280 x 320 column block and
one write of the 16 that leave it, (64 + 16) x 24 B x 1280 x 320 =
786,432,000 B (`floor_bytes`, the accepted reader's, from the shape), over
the self time of the step program's `merge` + `resegment` scopes and the
published HBM bandwidth; cannot pass 100 %. The accepted reader's own
code, loaded from its file (see `gs1024_sim_device_ms`)."""

import os

from chipbench import harness

NAME = "gs1024_composite_hbm_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "exchange + composite"
MOVES = "fps"
CELLS = ["gs1024-4rank-insitu"]
_ACCEPTED = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "composite_hbm_share.py"))
floor_bytes = _ACCEPTED.floor_bytes


def read(ctx):
    return _ACCEPTED.read(ctx)
