"""The session's `host_copy.start` span per frame (host clock): the loop
starts the device->host copy of every leaf of the frame it has just
dispatched (`copy_to_host_async`)."""

NAME = "host_copy_start_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    return loop_spans.named_ms(ctx, "host_copy.start")
