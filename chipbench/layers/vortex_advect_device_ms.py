"""Device time per frame of the vortex sim program's ops whose innermost
`sitpu_*` scope is `sim_advect` (self time, averaged over the devices; the
join is chipbench/scopes.py's, with the table the sim executable left on
the recorder): the semi-Lagrangian back-trace, that is the positions, the
wrap-padded copies of the three components (all-gathered on a mesh) and the
24 gathers. Nothing from a program that keeps no scope table."""

NAME = "vortex_advect_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["vortex256-4rank-insitu"]


def read(ctx):
    from chipbench import sim_scopes

    return sim_scopes.sim_scope_ms(ctx, "sim_advect")
