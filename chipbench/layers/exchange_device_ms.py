"""Device time per frame of the frame-step program's ops whose innermost
`sitpu_*` scope is `halo`, `exchange` or `wire_encode`: what moves
supersegments and slab faces between ranks, with the encode and decode of
a narrower wire (self time, averaged over the devices). `collective_ms`
beside it is the time a collective was in flight on device 0, overlapped
or not."""

NAME = "exchange_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "exchange + composite"
MOVES = "fps"
CELLS = ["gs512-4rank-insitu"]


def read(ctx):
    from chipbench import scopes

    return scopes.step_scope_ms(ctx, "halo", "exchange", "wire_encode")
