"""Share of the chunks a frame's write marches fold that the fold kernel
shades itself, from the march's one-channel value plane (the shaded
`rgba f32[C, 4, Nj, Ni]` chunk then never crosses HBM between the march
and the fold): the program's counters `fold_chunks_fused` over
`fold_chunks`, which a recorded step notes while it is first traced and
adds on every call. 100 where `slicer.fold=auto` took the shade-in-kernel
path (a TPU, a scalar volume, a concrete transfer function), 0 where the
march hands over shaded rgba (`pallas_seg`: a pre-shaded volume, a traced
transfer function). The harness hands a reader no counters, so they come
from the program's recorder. Nothing from a program that has no such
counter."""

import sys

NAME = "fold_fused_share"
UNIT = "%"
SOURCE = "program_counter"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from scenery_insitu_tpu import obs

    counters = obs.get_recorder().counters
    folded = counters.get("fold_chunks")
    if not folded:
        print("[chipbench] MISSING SOURCE: no `fold_chunks` counter on "
              "the recorder", file=sys.stderr)
        return None
    fused = counters.get("fold_chunks_fused", 0)
    print(f"[chipbench] fold_chunks (whole run): {folded}, fused {fused}",
          file=sys.stderr)
    return 100.0 * fused / folded
