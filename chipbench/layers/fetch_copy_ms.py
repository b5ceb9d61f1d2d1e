"""The session's `fetch.copy` spans per frame (host clock): inside `fetch`,
after the device is done, until every leaf of the frame is a numpy array -
the rest of the device-to-host copy; on a mesh one span per shard, summed.
Nothing from a program that has no such span."""

NAME = "fetch_copy_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "fetch.copy")
