"""`sim_device_ms` read in `gs1024-4rank-insitu`: device time per frame of
the sim-advance program (`programs.sim`: the fused stencil on every rank's
1024 x 1024 x 256 shard with its ring halos, tiles (16, 64)), averaged
over the devices. The accepted reader's own code, loaded from its file:
its `workloads` list is an entry this cell's PR could not touch, and a
`benchmark` PR that widens it deletes this file."""

import os

from chipbench import harness

NAME = "gs1024_sim_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["gs1024-4rank-insitu"]
_ACCEPTED = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "sim_device_ms.py"))


def read(ctx):
    return _ACCEPTED.read(ctx)
