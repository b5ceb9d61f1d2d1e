"""Per cent of the frames `d2h_GB_per_s` reads (those the host `waited`
for) whose transfer ran beside a newer frame's programs from its start to
its end (`beside0` on the frame's first `fetch.copy` span and `beside1` on
its last; PR 43). Beside `d2h_GB_per_s`'s classes it says which of the two
rates a cell's frames pay."""

import os

from chipbench import harness

NAME = "d2h_beside_share"
UNIT = "%"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = ["gs512-insitu", "gs512-4rank-insitu", "shm512-ingest",
         "vortex256-4rank-insitu", "gs1024-4rank-insitu"]
_RATE = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "d2h_GB_per_s.py"))


def read(ctx):
    found = _RATE.transfers(ctx)
    if not found:
        return None
    return 100.0 * sum(c == "beside" for _, _, c in found) / len(found)
