"""Median interval between consecutive sink deliveries of the (traced)
window, on the sink's clock: the typical frame, which a rare long stall
does not move. `fps` is all the frames over all the time and does move;
read the two together (one run of 46 lost 10.9 s of its window to a stall:
7.71 frames/s over time against a median interval of 104.3 ms; PERF.md,
PR 23)."""

NAME = "frame_median_ms"
UNIT = "ms"
SOURCE = "host_clock"
LAYER = "entry"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    import statistics

    stamps = ctx["stamps"]
    if len(stamps) < 2:
        return None
    return statistics.median(
        (b - a) * 1e3 for a, b in zip(stamps[:-1], stamps[1:]))
