"""Device time per frame of the vortex sim program's ops whose innermost
`sitpu_*` scope is `sim_field` (self time, averaged over the devices; the
join is chipbench/scopes.py's, with the table the sim executable left on
the recorder): the rendered field, that is |curl u| by central differences
(rolls: halo collectives on a mesh), its largest value (an all-reduce) and
the divide. Nothing from a program that keeps no scope table."""

NAME = "vortex_field_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["vortex256-4rank-insitu"]


def read(ctx):
    from chipbench import sim_scopes

    return sim_scopes.sim_scope_ms(ctx, "sim_field")
