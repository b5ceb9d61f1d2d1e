"""Device time per frame of the frame-step program's ops whose innermost
`sitpu_*` scope is `fold`: the supersegment fold of the marched chunks
(the Pallas kernel `sitpu_fold_*` or its XLA twin), turning its state into
the VDI's slots, and the threshold controller (self time, averaged over the
devices). 0 from a program that has no such scope."""

NAME = "fold_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.step_scope_ms(ctx, "fold")
