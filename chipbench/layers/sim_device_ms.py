"""Device time of the sim-advance program per frame, from the trace (the
program whose name matches the configuration's `programs.sim`), averaged
over the devices."""

NAME = "sim_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    return ctx["trace"].program_ms_per_run(
        ctx["config"]["programs"]["sim"])
