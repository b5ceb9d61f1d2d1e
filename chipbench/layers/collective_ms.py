"""Time per frame in which a collective (halo permute, all-to-all) was in
flight on device 0, from the trace."""

NAME = "collective_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "exchange + composite"
MOVES = "fps"
CELLS = ["gs512-4rank-insitu"]


def read(ctx):
    runs = ctx["trace"].program_runs(ctx["config"]["programs"]["step"])
    total, _ = ctx["trace"].collective_s()
    return total / runs * 1e3 if runs and total else None
