"""The slowest shard's `fetch.copy` per frame (host clock): per frame the
spans are summed by their `shard`, the largest sum is the frame's, and the
frames are averaged."""

NAME = "fetch_shard_max_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = ["gs512-4rank-insitu"]


def read(ctx):
    frames = {}
    for e in ctx["spans"]:
        shard = (e.get("attrs") or {}).get("shard")
        if e["name"] == "fetch.copy" and shard is not None:
            per = frames.setdefault(e.get("frame"), {})
            per[shard] = per.get(shard, 0.0) + e["dur"]
    if not frames:
        return None
    return sum(max(per.values()) for per in frames.values()) \
        / len(frames) * 1e3
