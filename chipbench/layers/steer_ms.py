"""The session's `steer` span per frame (host clock): the steering drain,
with the camera a message describes put on the device. Read through the
loop thread's spans like the rest of the loop's accounting, so nothing from
a program whose spans carry no `thread`."""

NAME = "steer_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    return loop_spans.named_ms(ctx, "steer")
