"""Distance of the vortex sim program from the traffic no schedule can
avoid: the velocity u (3 x f32) read once and written once PER STEP and
the rendered field (f32) written once per frame (`floor_bytes`, over the
ranks), over the sim program's device time and the published HBM
bandwidth. The same work whatever implements it; every schedule moves
more (the back-trace gathers, the transforms' passes), so this cannot
pass 100 %."""

NAME = "vortex_sim_hbm_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "sim"
MOVES = "fps"
CELLS = ["vortex256-4rank-insitu"]


def floor_bytes(shape: dict) -> int:
    """Bytes one frame's sim must move over all ranks: (2 x 3 x steps + 1)
    f32 grids (469,762,048 B for 256^3 at one step)."""
    d, h, w = shape["grid"]
    return (2 * 3 * shape["steps_per_frame"] + 1) * 4 * d * h * w


def read(ctx):
    ms = ctx["trace"].program_ms_per_run(ctx["config"]["programs"]["sim"])
    if not ms or not ctx["peaks"]:
        return None
    floor = floor_bytes(ctx["shape"]) / ctx["shape"]["ranks"]
    return floor / (ms / 1e3) / (ctx["peaks"]["hbm_gbps"] * 1e9) * 100.0
