"""Device time per frame of the frame-step program's ops whose innermost
`sitpu_*` scope is `fold`: the supersegment fold of the marched chunks
(the Pallas kernel `sitpu_fold_*` or its XLA twin), turning its state into
the VDI's slots, and the threshold controller (self time, averaged over the
devices). Nothing from a program that keeps no scope table."""

NAME = "fold_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.step_scope_ms(ctx, "fold")
