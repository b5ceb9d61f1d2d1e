"""The rate of a frame's device-to-host copy where the spans give its start
and its end exactly (PR 43): a frame whose `fetch.copy` spans say `waited`
(the host entered `fetch.ready` before the frame's programs were done) began
its transfer when `fetch.ready` ended and had its last byte when the frame's
last `fetch.copy` ended. The window's bytes of such frames over their time
between those two moments, in GB/s (equal frames: the time-weighted mean of
their rates). `fetch_copy_ms` beside it is the REST of a copy the host
happened to wait for, whenever it began.

To stderr the same rate by class, with counts: `beside` (a newer frame's
programs were in flight when the first copy's wait began, `beside0`, and
still when the last one's ended, `beside1`), `alone` (neither), `part`.
Nothing from a program whose `fetch.copy` spans carry no `waited`, and
nothing from a window in which the host never waited for a frame."""

import sys

NAME = "d2h_GB_per_s"
UNIT = "GB/s"
SOURCE = "program_span"
LAYER = "delivery"
MOVES = "fps"
CELLS = ["gs512-insitu", "gs512-4rank-insitu", "shm512-ingest",
         "vortex256-4rank-insitu", "gs1024-4rank-insitu"]


def transfers(ctx):
    """[(bytes, seconds, class)] of the window's frames that were `waited`
    for (kept in `ctx`); None, with a line where spans exist, if no
    `fetch.copy` span carries the attribute."""
    from chipbench import scopes

    if "_transfers" in ctx:
        return ctx["_transfers"]
    copies, ready_end = {}, {}
    for e in ctx["spans"]:
        if e["name"] == "fetch.copy" and "waited" in (e.get("attrs") or {}):
            copies.setdefault(e.get("frame"), []).append(e)
        elif e["name"] == "fetch.ready":
            ready_end[e.get("frame")] = e["ts"] + e["dur"]
    if not copies:
        if ctx["spans"]:
            scopes._missing("no `fetch.copy` span carries `waited`")
        ctx["_transfers"] = None
        return None
    found = []
    for frame, spans in copies.items():
        spans.sort(key=lambda e: e["ts"])
        first, last = spans[0]["attrs"], spans[-1]["attrs"]
        if not first["waited"] or frame not in ready_end:
            continue
        seconds = spans[-1]["ts"] + spans[-1]["dur"] - ready_end[frame]
        marks = {bool(first["beside0"]), bool(last["beside1"])}
        found.append((sum(e["attrs"]["bytes"] for e in spans), seconds,
                      "part" if len(marks) == 2 else
                      "beside" if marks == {True} else "alone"))
    print(f"[chipbench] transfers: {len(found)} of {len(copies)} fetched "
          f"frames were waited for (the others' programs were done before "
          f"the host asked: their transfer's start has no span)",
          file=sys.stderr, flush=True)
    ctx["_transfers"] = found
    return found


def rate(found) -> float:
    return sum(b for b, _, _ in found) / sum(s for _, s, _ in found) / 1e9


def read(ctx):
    found = transfers(ctx)
    if not found:
        return None
    by_class = {}
    for t in found:
        by_class.setdefault(t[2], []).append(t)
    print("[chipbench] d2h_GB_per_s by class: " + ", ".join(
        f"{k}: {rate(v):.3f} GB/s x {len(v)} "
        f"({sum(s for _, s, _ in v) / len(v) * 1e3:.2f} ms a frame)"
        for k, v in sorted(by_class.items())), file=sys.stderr, flush=True)
    return rate(found)
