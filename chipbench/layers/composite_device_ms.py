"""Device time per frame of the frame-step program's ops whose innermost
`sitpu_*` scope is `merge` or `resegment`: the sort-last composite (self
time, averaged over the devices)."""

NAME = "composite_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.step_scope_ms(ctx, "merge", "resegment")
