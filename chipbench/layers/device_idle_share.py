"""1 - union of device-busy intervals over the traced window, device 0."""

NAME = "device_idle_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    return ctx["trace"].idle_share() * 100.0
