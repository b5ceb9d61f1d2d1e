"""Mean duration of the `dispatch` spans opened with a transfer in flight
(host clock): `upload_busy` (an upload between `device_put` and landed) or
`prev_ready` (the frame before computed, its device->host copy under way or
done). Beside `step_dispatch_ms` it says what a launch behind a transfer
costs. The mean and count of each of the four classes go to stderr, and with
them the launches that were not marked `upload_busy` although an
`ingest.upload` span of another thread began while their `dispatch` span was
open: the uploader is released by the same `advance` that precedes the
launch, so the flag, read just before the span opens, can miss an upload by
a millisecond that the launch then waits behind all the same."""

import sys

NAME = "step_dispatch_busy_ms"
UNIT = "ms"
SOURCE = "program_span"
LAYER = "ingest"
MOVES = "fps"
CELLS = ["shm512-ingest"]


def read(ctx):
    from chipbench import loop_spans

    marked = loop_spans.launches(ctx, "upload_busy")
    if marked is None:
        return None
    classes = {}
    for e in marked:
        key = (bool(e["attrs"]["upload_busy"]),
               bool(e["attrs"].get("prev_ready")))
        classes.setdefault(key, []).append(e["dur"] * 1e3)
    print("[chipbench] dispatch spans by (upload_busy, prev_ready): "
          + ", ".join(f"{k}: {sum(v) / len(v):.3f} ms x {len(v)}"
                      for k, v in sorted(classes.items())),
          file=sys.stderr, flush=True)
    ups = [u["ts"] for u in ctx["spans"] if u["name"] == "ingest.upload"]
    met = [e["dur"] * 1e3 for e in marked if not e["attrs"]["upload_busy"]
           and any(e["ts"] <= t < e["ts"] + e["dur"] for t in ups)]
    if met:
        print(f"[chipbench] launches not marked upload_busy during which "
              f"an upload began: {len(met)}, {sum(met) / len(met):.3f} ms",
              file=sys.stderr, flush=True)
    busy = [d for k, v in classes.items() if any(k) for d in v]
    return sum(busy) / len(busy) if busy else None
