"""Share of the slot rows the fold kernel's K-loops visit that they
merge: the program's counters `fold_slot_rows_merged` over
`fold_slot_rows`. The kernel walks each 8 x 128 tile's K slot rows once a
chunk; it merges only the rows some sample of the tile landed in (the
hull of its pixels' slot intervals) and copies the others, and says how
many of each through a small i32 output that a recorded step hands on
with the frame. 100 where every tile's samples span all K slots, 0 for a
frame with no live sample and where no kernel folds (the XLA fold of a
rehearsal off a TPU visits no row); the kernel's phase B costs about this
share of what it cost dense. A tile is a 128-lane block inside the
image's width; on a mesh the counters are each rank's own, summed. The
harness hands a reader no counters, so they come from the program's
recorder. Nothing from a program that has no such counter. See
README_fold_slots.md."""

import sys

NAME = "fold_slot_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from scenery_insitu_tpu import obs

    counters = obs.get_recorder().counters
    rows = counters.get("fold_slot_rows")
    if rows is None:
        print("[chipbench] MISSING SOURCE: no `fold_slot_rows` counter on "
              "the recorder", file=sys.stderr)
        return None
    merged = counters.get("fold_slot_rows_merged", 0)
    print(f"[chipbench] fold_slot_rows (whole run): {rows}, merged "
          f"{merged}", file=sys.stderr)
    return 100.0 * merged / rows if rows else 0.0
