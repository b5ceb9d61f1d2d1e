"""`dataset_load_s` read in `beechnut-u16-view`: seconds of the session's
`dataset.load` span (3.24 GB read in 8 z-slabs and put on the device at
u16; `read_s` / `put_s` on stderr), taken from the program's recorder.
The accepted reader's own code, loaded from its file: its `workloads` list
is an entry this cell's PR could not touch, and a `benchmark` PR that
widens it deletes this file."""

import os

from chipbench import harness

NAME = "beechnut_load_s"
UNIT = "s"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "setup_s"
CELLS = ["beechnut-u16-view"]
_ACCEPTED = harness.load_file("layer", os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "dataset_load_s.py"))


def read(ctx):
    return _ACCEPTED.read(ctx)
