"""The session's `fetch` span per frame (host clock): waiting for the frame
on the device, its device-to-host copy and, on a mesh, the host
concatenation of the shards."""

NAME = "fetch_wait_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = "all"


def read(ctx):
    waits = [e["dur"] for e in ctx["spans"] if e["name"] == "fetch"]
    return sum(waits) / ctx["frames"] * 1e3 if waits else None
