"""Per cent of the window's `dispatch` spans opened with `prev_ready` true:
the device had already finished the frame before when the host launched this
one. The program-side twin of `device_idle_share`: such a launch is a frame
the host was late for."""

NAME = "launch_prev_ready_share"
UNIT = "%"
SOURCE = "program_span"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import loop_spans

    marked = loop_spans.launches(ctx, "prev_ready")
    return None if marked is None else loop_spans.share(marked, "prev_ready")
