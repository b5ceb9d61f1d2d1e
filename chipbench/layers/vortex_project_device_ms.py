"""Device time per frame of the vortex sim program's ops whose innermost
`sitpu_*` scope is `sim_project` (self time, averaged over the devices; the
join is chipbench/scopes.py's, with the table the sim executable left on
the recorder): the spectral viscous decay and the Leray projection, that is
three forward and three inverse real transforms over all three axes (DFT
matmuls on a TPU) with what moves their operands between ranks. Nothing
from a program that keeps no scope table."""

NAME = "vortex_project_device_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["vortex256-4rank-insitu"]


def read(ctx):
    from chipbench import sim_scopes

    return sim_scopes.sim_scope_ms(ctx, "sim_project")
