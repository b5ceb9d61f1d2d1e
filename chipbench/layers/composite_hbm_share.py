"""Distance of the sort-last composite from the traffic no schedule can
avoid: per device one read of the R*K supersegments that arrive for its
column block and one write of the K that leave it (`floor_bytes`), over
the self time of the step program's `merge` + `resegment` scopes
(averaged over the devices) and the published HBM bandwidth. The sort
and the fold re-read the stream many times, so today this is a fraction
of a percent; it cannot pass 100 %."""

NAME = "composite_hbm_share"
UNIT = "%"
SOURCE = "device_trace"
LAYER = "exchange + composite"
MOVES = "fps"
CELLS = ["gs512-4rank-insitu"]


def floor_bytes(shape: dict) -> float:
    """Bytes one device's composite must move per frame: (R*K in + K out)
    slots of `bytes_per_slot` on its ni/R x nj column block."""
    from chipbench import arith

    ni, nj = arith.intermediate_grid(shape)
    ranks, k = shape["ranks"], shape["k"]
    return (ranks * k + k) * shape["bytes_per_slot"] * (ni // ranks) * nj


def read(ctx):
    from chipbench import scopes

    ms = scopes.step_scope_ms(ctx, "merge", "resegment")
    if not ms or not ctx["peaks"]:
        return None
    return (floor_bytes(ctx["shape"]) / (ms / 1e3)
            / (ctx["peaks"]["hbm_gbps"] * 1e9) * 100.0)
