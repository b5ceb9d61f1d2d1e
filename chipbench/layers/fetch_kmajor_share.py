"""Per cent of the window's frames fetched from a mesh whose every sharded
leaf left it cut along its leading axis alone: each `fetch.concat` span of
the frame carries `kmajor` true (every shard's `index` leaves all axes but
the first whole, so the host's assembly is a few contiguous copies, not
rows of one block between rows of the others). A frame with one leaf cut
any other way is not counted."""

NAME = "fetch_kmajor_share"
UNIT = "%"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = ["gs512-4rank-insitu", "vortex256-4rank-insitu"]


def read(ctx):
    from chipbench import scopes

    frames = {}
    for e in ctx["spans"]:
        attrs = e.get("attrs") or {}
        if e["name"] == "fetch.concat" and "kmajor" in attrs:
            frames[e.get("frame")] = (frames.get(e.get("frame"), True)
                                      and bool(attrs["kmajor"]))
    if not frames:
        if ctx["spans"]:
            scopes._missing("no `fetch.concat` span carries `kmajor`")
        return None
    return 100.0 * sum(frames.values()) / len(frames)
