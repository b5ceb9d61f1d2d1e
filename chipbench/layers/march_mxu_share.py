"""Dense banded-matmul FLOPs of the slice march
(arith.march_dense_flops_per_frame, per device) over the frame-step
program's device time and the published bf16 peak. Skipping levers
execute fewer than the dense count."""

NAME = "march_mxu_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    from chipbench import arith

    ms = ctx["trace"].program_ms_per_run(ctx["config"]["programs"]["step"])
    if not ms or not ctx["peaks"]:
        return None
    flops = (arith.march_dense_flops_per_frame(ctx["shape"])
             / ctx["shape"]["ranks"])
    return flops / (ms / 1e3) / (ctx["peaks"]["bf16_tflops"] * 1e12) * 100.0
