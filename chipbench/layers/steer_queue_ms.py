"""Median wait of a camera message between the viewer handing it over and
the `steer` span that drained it (host clock; `loop_spans.steer_parts`): the
first part of `steer_to_pixel_ms`."""

NAME = "steer_queue_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "steer_to_pixel_ms"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    return loop_spans.steer_part_ms(ctx, 0)
