"""Device time per frame of the frame-step program's ops whose innermost
`sitpu_*` scope is `march` (self time, averaged over the devices; the join
is chipbench/scopes.py's). That is the resampling matmuls and the shading
of the slice march, which the source scopes, AND what runs inside the
march's chunk loop with no `op_name` of its own and so inherits the loop's
phase: the update-slice fusions and prefetch copies the compiler makes to
stage a chunk for the fold (12.8 of 36.4 ms at 512^3, 0.005 of 2.18 at
128^3: PERF.md section 5). The run's log splits the two ("of which by
inheritance"); a change to the fold's input layout moves this metric, not
`fold_device_ms`. Nothing from a program that keeps no scope table."""

NAME = "march_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import scopes

    return scopes.step_scope_ms(ctx, "march")
