"""The session's `fetch.concat` spans per frame (host clock): the host
assembles the shards of a frame that is sharded over the mesh."""

NAME = "fetch_concat_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = ["gs512-4rank-insitu"]


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "fetch.concat")
