"""What the loop's thread does per frame under no span (host clock): the
iteration, `window_s / frames`, less the thread's `depth` 0 spans (`steer`,
`replan`, `sim`, `dispatch`, `host_copy.start`, `release`, `fetch`, `sinks`,
`upkeep`). What is left is the loop's own statements between its spans, the
early returns of `_maybe_replan`, and `run`'s end, once per window. Nothing
from a program whose spans carry no `thread`."""

NAME = "host_unspanned_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    spans = loop_spans.loop(ctx)
    if spans is None:
        return None
    top = [e for e in spans if e["depth"] == 0]
    return loop_spans.interval_ms(ctx) - loop_spans.per_frame_ms(ctx, top)
