"""Per cent of the window's frames fetched from a mesh for which the host's
pool had to allocate: a `fetch.concat` span of the frame carries `fresh`
true (`HostFrames.take` found no array that nobody holds: the first frame,
and a frame after one that a sink kept)."""

NAME = "fetch_fresh_share"
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
        if e["name"] == "fetch.concat" and "fresh" in attrs:
            frames[e.get("frame")] = (frames.get(e.get("frame"), False)
                                      or bool(attrs["fresh"]))
    if not frames:
        if ctx["spans"]:
            scopes._missing("no `fetch.concat` span carries `fresh`")
        return None
    return 100.0 * sum(frames.values()) / len(frames)
