"""Median time from the start of the `steer` span that drained a camera
message to the start of the `sinks` span of the first frame rendered from it
(host clock; `loop_spans.steer_parts`): the second part of
`steer_to_pixel_ms`, the frames in flight."""

NAME = "steer_frame_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "steer_to_pixel_ms"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    return loop_spans.steer_part_ms(ctx, 1)
