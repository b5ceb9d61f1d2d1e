"""The session's `camera_readback` spans per frame (host clock): every host
read of a camera leaf that lives on the device (`choose_axis` at dispatch,
the defaults of a camera message) - each waits for the device. 0 from a
program that has no such span."""

NAME = "camera_readback_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "camera_readback")
