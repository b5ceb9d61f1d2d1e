"""The rate of the host -> device hop: the bytes of the window's
`ingest.upload` spans over the time inside them (`uploads.window`). No
peak of the host link is in `peaks.json`, so it is a rate and not a share.
Nothing from a program that has no such span."""

NAME = "upload_GB_per_s"
UNIT = "GB/s"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "fps"
CELLS = ["shm512-ingest"]


def read(ctx):
    from chipbench import uploads

    nbytes, seconds = uploads.window(ctx)
    if not seconds:
        return None
    return nbytes / seconds / 1e9
