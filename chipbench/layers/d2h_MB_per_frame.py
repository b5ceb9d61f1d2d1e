"""Bytes of the payload arrays the sink received, per frame (a count)."""

NAME = "d2h_MB_per_frame"
UNIT = "MB"
SOURCE = "program_counter"
LAYER = "delivery"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    return sum(ctx["nbytes"]) / len(ctx["nbytes"]) / 1e6
