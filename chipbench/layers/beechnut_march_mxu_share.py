"""The march's share of the MXU's peak in `beechnut-u16-view`, by the
passes the program EXECUTES: its counter `march_operand_planes` over the
frames it dispatched (2 where a u16 chunk meets the matmuls as its two
byte planes) gives `arith_dataset16.march_executed_flops_per_frame` (two
byte planes into the first resampling contraction, two bfloat16 terms of
the f32 intermediate into the second: twice the dense count, 18.675e12 a
frame) over the device time of the step program's `march` scope and the
published bf16 peak. The generic `march_mxu_share` counts ONE pass of
each contraction over the WHOLE step program and under-reads here by
more than half. Dense: the occupancy gates skip some of it, so the
executed share is at most this. The harness hands a reader no counters,
so the counter and the `dispatch` spans come from the program's recorder.
Nothing from a program that has no such counter (PR 49's parent) or
keeps no scope table."""

import sys

NAME = "beechnut_march_mxu_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "march + fold + composite"
MOVES = "fps"
CELLS = ["beechnut-u16-view"]


def read(ctx):
    from chipbench import arith_dataset16, scopes
    from scenery_insitu_tpu import obs

    rec = obs.get_recorder()
    planes = rec.counters.get("march_operand_planes")
    frames = sum(1 for e in rec.events
                 if e["type"] == "span" and e["name"] == "dispatch")
    if not planes or not frames:
        print("[chipbench] MISSING SOURCE: no `march_operand_planes` "
              "counter on the recorder", file=sys.stderr)
        return None
    print(f"[chipbench] march_operand_planes (whole run): {planes} over "
          f"{frames} dispatched frames", file=sys.stderr)
    ms = scopes.step_scope_ms(ctx, "march")
    if not ms or not ctx["peaks"]:
        return None
    flops = (arith_dataset16.march_executed_flops_per_frame(
        ctx["shape"], planes / frames) / ctx["shape"]["ranks"])
    return flops / (ms / 1e3) / (ctx["peaks"]["bf16_tflops"] * 1e12) * 100.0
