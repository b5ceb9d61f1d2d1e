"""The shm source's `ingest.wait` spans per frame (host clock): inside
`sim`, the frame loop waiting for a field to have landed on the device.
Where the host link paces the frame this is the interval less the host's
serial work; about 0 where the upload hides behind march + fold. Nothing
from a program that has no such span."""

NAME = "ingest_wait_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "fps"
CELLS = ["shm512-ingest"]


def read(ctx):
    from chipbench import scopes

    return scopes.span_ms(ctx, "ingest.wait")
