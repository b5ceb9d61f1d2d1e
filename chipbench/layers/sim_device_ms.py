"""Device time of the sim-advance program per frame, from the trace (the
program whose name matches the configuration's `programs.sim`), averaged
over the devices. Read in the cells whose traffic advances a sim on the
device every frame: a window without such a program has nothing to read,
and one that hands its state back unchanged (0 steps) would read as a
time."""

NAME = "sim_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["gs512-insitu", "gs128-insitu", "gs512-4rank-insitu"]


def read(ctx):
    return ctx["trace"].program_ms_per_run(
        ctx["config"]["programs"]["sim"])
