"""Device time of the frame-step program (march, fold, exchange,
composite) per frame, from the trace (`programs.step`), averaged over the
devices."""

NAME = "step_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    return ctx["trace"].program_ms_per_run(
        ctx["config"]["programs"]["step"])
