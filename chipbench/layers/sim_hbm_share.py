"""Distance from a fully fused advance: one read and one write of u and v,
f32, PER FRAME (arith.sim_floor_bytes_per_frame, per device) over the sim
program's device time and the published HBM bandwidth. No schedule moves
fewer bytes, so this cannot pass 100 %."""

NAME = "sim_hbm_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["gs512-insitu", "gs128-insitu", "gs512-4rank-insitu"]


def read(ctx):
    from chipbench import arith

    ms = ctx["trace"].program_ms_per_run(ctx["config"]["programs"]["sim"])
    if not ms or not ctx["peaks"]:
        return None
    floor = (arith.sim_floor_bytes_per_frame(ctx["shape"])
             / ctx["shape"]["ranks"])
    return floor / (ms / 1e3) / (ctx["peaks"]["hbm_gbps"] * 1e9) * 100.0
