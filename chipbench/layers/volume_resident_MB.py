"""Megabytes of every copy of the dataset volume the session holds on its
fullest device, at the dtype each is held at: the program's counter
`volume_resident_bytes`, bumped once under `dataset.load` (834 for
Kingsnake's u8; 3,335 widened to float32). Beside it on stderr the
counter `volume_copies_per_frame` per frame of the run: instructions of
the step program that write an array as large as the field, 0 where the
march reads the field where it lives. The harness hands a reader no
counters, so both come from the program's recorder. Nothing from a
program that has no such counter."""

import sys

NAME = "volume_resident_MB"
UNIT = "MB"
SOURCE = "program_counter"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = ["kingsnake-u8-view"]


def read(ctx):
    from scenery_insitu_tpu import obs

    counters = obs.get_recorder().counters
    held = counters.get("volume_resident_bytes")
    if held is None:
        print("[chipbench] MISSING SOURCE: no `volume_resident_bytes` "
              "counter on the recorder", file=sys.stderr)
        return None
    print(f"[chipbench] volume_copies_per_frame (whole run): "
          f"{counters.get('volume_copies_per_frame')}", file=sys.stderr)
    return held / 1e6
