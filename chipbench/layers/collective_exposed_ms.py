"""The part of collective_ms in which no other op ran on device 0."""

NAME = "collective_exposed_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "exchange + composite"
MOVES = "fps"
CELLS = ["gs512-4rank-insitu"]


def read(ctx):
    runs = ctx["trace"].program_runs(ctx["config"]["programs"]["step"])
    total, exposed = ctx["trace"].collective_s()
    return exposed / runs * 1e3 if runs and total else None
