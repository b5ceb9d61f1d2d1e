"""Time per frame in which a collective was in flight on device 0 INSIDE
the Gray-Scott sim program (`programs.sim`), overlapped or not: the ring
halos of the fused stencil's passes, `collective-permute`s of T planes of
u and v each way (3 passes a frame at 10 steps: 4 + 4 + 2 planes of
1024 x 1024). The code of `vortex_sim_collective_ms`, which reads the
same of its own cell's sim program, loaded from its file."""

import os

from chipbench import harness

NAME = "gs1024_sim_halo_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["gs1024-4rank-insitu"]
_ACCEPTED = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "vortex_sim_collective_ms.py"))


def read(ctx):
    return _ACCEPTED.read(ctx)
