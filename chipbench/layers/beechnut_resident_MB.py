"""`volume_resident_MB` read in `beechnut-u16-view`: the program's counter
`volume_resident_bytes` / 1e6, every copy of the volume on the fullest
device at the dtype it is held at (3,242.2 = one copy at u16; widened to
float32 it would read 6,484.4); `volume_copies_per_frame` on stderr.
The accepted reader's own code, loaded from its file: its `workloads` list
is an entry this cell's PR could not touch, and a `benchmark` PR that
widens it deletes this file."""

import os

from chipbench import harness

NAME = "beechnut_resident_MB"
UNIT = "MB"
SOURCE = "program_counter"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = ["beechnut-u16-view"]
_ACCEPTED = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "volume_resident_MB.py"))


def read(ctx):
    return _ACCEPTED.read(ctx)
