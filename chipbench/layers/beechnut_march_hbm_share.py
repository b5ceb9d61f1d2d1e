"""`march_hbm_share` read in `beechnut-u16-view`: the least HBM traffic of one
frame's march + fold (`arith_dataset.march_floor_bytes_per_frame`: the volume
read once at u16, 3,242,196,992 B, and the VDI written once, 786,432,000 B:
4,028,628,992 B) over the device time of the step program's `march` and
`fold` scopes and the published HBM peak; cannot pass 100 %.
The accepted reader's own code, loaded from its file: its `workloads` list
is an entry this cell's PR could not touch, and a `benchmark` PR that
widens it deletes this file."""

import os

from chipbench import harness

NAME = "beechnut_march_hbm_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = ["beechnut-u16-view"]
_ACCEPTED = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "march_hbm_share.py"))


def read(ctx):
    return _ACCEPTED.read(ctx)
