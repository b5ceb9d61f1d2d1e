"""Per cent of the window's `dispatch` spans opened with `upload_busy` true:
the shm source's uploader was between `device_put` and landed when the host
launched the frame."""

NAME = "launch_upload_busy_share"
UNIT = "%"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "fps"
CELLS = ["shm512-ingest"]


def read(ctx):
    from chipbench import loop_spans

    marked = loop_spans.launches(ctx, "upload_busy")
    return None if marked is None else loop_spans.share(marked, "upload_busy")
