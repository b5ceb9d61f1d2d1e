"""Time per frame in which a collective (all-gather, all-to-all, all-reduce,
permute) was in flight on device 0 INSIDE the vortex sim program, overlapped
or not, from the trace. `collective_ms` reads the whole window, the step
program's all-to-all included, and lists its own cell."""

NAME = "vortex_sim_collective_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["vortex256-4rank-insitu"]


def read(ctx):
    from chipbench import sim_scopes

    pattern = ctx["config"]["programs"]["sim"]
    runs = ctx["trace"].program_runs(pattern)
    return (sim_scopes.sim_collective_s(ctx["trace"], pattern) / runs * 1e3
            if runs else None)
