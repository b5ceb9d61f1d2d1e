"""Bytes the shm source put on the device per frame (a count): the `bytes`
attribute of the window's `ingest.upload` spans (`uploads.window`, which
`upload_GB_per_s` divides by their time). The program's counter
`ingest_bytes` counts the same bytes inside the same spans; the readers'
`ctx` holds spans and no counters. Nothing from a program that has no such
span."""

NAME = "h2d_MB_per_frame"
UNIT = "MB"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "fps"
CELLS = ["shm512-ingest"]


def read(ctx):
    from chipbench import uploads

    nbytes, _ = uploads.window(ctx)
    if nbytes is None:
        return None
    return nbytes / ctx["frames"] / 1e6
