"""The loop's bookkeeping per frame (host clock): its `upkeep` spans (after
the dispatch: the frame's metadata kept, old ones pruned, camera and index
advanced; after the retire: the timers' window, the SLO engine's sample,
the collector's batch) and its `release` spans (the payload of the frame
before let go; the retired frame's device arrays let go), counted once where
one lies inside the other."""

NAME = "loop_upkeep_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    spans = loop_spans.loop(ctx)
    upkeep = loop_spans.named_ms(ctx, "upkeep")
    if upkeep is None:
        return None
    beside = [e for e in spans if e["name"] == "release"
              and e.get("parent") != "upkeep"]
    return upkeep + loop_spans.per_frame_ms(ctx, beside)
