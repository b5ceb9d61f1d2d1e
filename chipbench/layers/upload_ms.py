"""The shm source's `ingest.upload` spans per frame (host clock, on the
uploader's thread, beside the frame loop): slot pinned -> transfer landed
on the device. Nothing from a program that has no such span."""

NAME = "upload_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "fps"
CELLS = ["shm512-ingest"]


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "ingest.upload")
