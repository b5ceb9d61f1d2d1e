"""The session's `dispatch` span per frame (host clock): the host dispatches
the frame-step program (with the `camera_readback` of `choose_axis` inside
it)."""

NAME = "step_dispatch_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "dispatch")
