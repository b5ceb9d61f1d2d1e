"""Median count of deliveries between handing a camera message to the
session and the first frame rendered from it (a count)."""

NAME = "frames_in_flight"
UNIT = "frames"
SOURCE = "program_counter"
LAYER = "entry"
MOVES = "steer_to_pixel_ms"
CELLS = "all"


def read(ctx):
    import statistics

    counts = [n for _, _, n in ctx["steers"]]
    return statistics.median(counts) if counts else None
