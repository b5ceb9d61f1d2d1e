"""Time per frame in which a collective was in flight on device 0 INSIDE
the step program (`programs.step`), overlapped or not, by opcode: the
column `all-to-all` (472 MB a rank at 1280 x 1280) and the frame's slot
exchange (157 MB a rank out). `collective_ms` reads the whole window, the
sim program's halo permutes included, and lists its own cell; here those
are `gs1024_sim_halo_ms`'s. Counted by `sim_scopes.sim_collective_s`, the
code the vortex cell's reader uses, with the step program's pattern."""

NAME = "gs1024_collective_ms"
UNIT = "ms"
SOURCE = "device_trace"
LAYER = "exchange + composite"
MOVES = "fps"
CELLS = ["gs1024-4rank-insitu"]


def read(ctx):
    from chipbench import sim_scopes

    pattern = ctx["config"]["programs"]["step"]
    runs = ctx["trace"].program_runs(pattern)
    return (sim_scopes.sim_collective_s(ctx["trace"], pattern) / runs * 1e3
            if runs else None)
