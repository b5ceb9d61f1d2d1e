"""The session's `sim` span per frame (host clock): the host dispatches the
frame's sim advance."""

NAME = "sim_dispatch_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "sim"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "sim")
