"""Device time per frame of the vortex sim program (the program whose name
matches the configuration's `programs.sim`: n steps and the rendered
field in one executable), from the trace, averaged over the devices."""

NAME = "vortex_sim_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["vortex256-4rank-insitu"]


def read(ctx):
    return ctx["trace"].program_ms_per_run(
        ctx["config"]["programs"]["sim"])
