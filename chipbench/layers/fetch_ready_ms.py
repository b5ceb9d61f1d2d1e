"""The session's `fetch.ready` spans per frame (host clock): inside `fetch`,
the host waits for the frame's device programs (`jax.block_until_ready`).
Nothing from a program that has no such span."""

NAME = "fetch_ready_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "fetch.ready")
