"""The march's bandwidth roofline at this shape: the least HBM traffic of
one frame's march + fold (arith_dataset.march_floor_bytes_per_frame: the
volume read once at its native dtype, the VDI written once) over the
device time of the step program's `march` and `fold` scopes and the
published HBM peak. It counts every op of the two scopes (the join is
chipbench/scopes.py's: the resampling matmuls and shading, the chunk
loop's staging copies by inheritance, the fold kernel, slots and the
threshold controller), not one kernel: the integer volume operand is the
same XLA matmul path the other cells run, with another convert. It
stands beside `march_mxu_share` (the dense matmul FLOPs over the whole
step program): a frame is bound by the larger of the two at most.
Nothing from a program that keeps no scope table."""

NAME = "march_hbm_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = ["kingsnake-u8-view"]


def read(ctx):
    from chipbench import arith_dataset, scopes

    ms = [scopes.step_scope_ms(ctx, s) for s in ("march", "fold")]
    if None in ms or not sum(ms) or not ctx["peaks"]:
        return None
    floor = arith_dataset.march_floor_bytes_per_frame(ctx["shape"])
    return (floor / (sum(ms) / 1e3)
            / (ctx["peaks"]["hbm_gbps"] * 1e9) * 100.0)
