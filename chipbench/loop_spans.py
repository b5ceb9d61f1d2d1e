"""What the host-side readers of PR 39 share: the spans of the frame loop's
own thread, a launch's own account of what was in flight, and a camera
message followed from its drain to its pixels.

The loop's thread is the thread of the window's `dispatch` spans (every span
event carries `thread`, the name of the thread that opened it: the shm
uploader's and the delivery worker's spans are `depth` 0 too and run beside
the loop). The iteration is `window_s / frames`, as `dispatch_ms` takes it:
no span covers a whole iteration, so that an idle gap of the device trace is
still named after the leaf span under it.

A program from before these spans gives nothing here, and every reader built
on this file returns None for it: where no span carries `thread`, `loop` says
so once (`MISSING SOURCE`) and the accounting readers, `steer_ms` among them,
are all left out, so that parent and change are never read by two
definitions. An attribute that no span carries reads as nothing as well.
"""

import statistics
import sys

from chipbench import scopes


def loop(ctx):
    """The loop thread's spans of the window (kept in `ctx`); None from a
    run that recorded no span, and None with a `MISSING SOURCE` line where
    the spans carry no `thread`."""
    if "_loop_spans" not in ctx:
        threads = {e["thread"] for e in ctx["spans"]
                   if e["name"] == "dispatch" and "thread" in e}
        if ctx["spans"] and not threads:
            scopes._missing(
                "the program's spans carry no `thread` (a commit from "
                "before it, or no `dispatch` span): the loop's thread "
                "cannot be told from the uploader's, so none of the loop "
                "accounting is read")
        ctx["_loop_spans"] = [e for e in ctx["spans"]
                              if e.get("thread") in threads] or None
    return ctx["_loop_spans"]


def interval_ms(ctx) -> float:
    return ctx["window_s"] / ctx["frames"] * 1e3


def per_frame_ms(ctx, spans) -> float:
    return sum(e["dur"] for e in spans) / ctx["frames"] * 1e3


def named_ms(ctx, name: str):
    """Ms per frame of the loop thread's spans called `name`; None (with a
    `MISSING SOURCE` line where the loop's spans are there) if none is."""
    spans = loop(ctx)
    if spans is None:
        return None
    named = [e for e in spans if e["name"] == name]
    if not named:
        scopes._missing("the loop's thread recorded no span called "
                        f"{name!r}")
        return None
    return per_frame_ms(ctx, named)


def launches(ctx, attr: str):
    """The window's `dispatch` spans that carry the boolean `attr`
    (`prev_ready`, `upload_busy`); None, with a line where spans exist, if
    none does."""
    marked = [e for e in ctx["spans"] if e["name"] == "dispatch"
              and attr in (e.get("attrs") or {})]
    if ctx["spans"] and not marked:
        scopes._missing(f"no `dispatch` span carries {attr!r}")
    return marked or None


def share(spans, attr: str) -> float:
    """Per cent of `spans` whose `attr` is true."""
    return 100.0 * sum(bool(e["attrs"][attr]) for e in spans) / len(spans)


def steer_parts(ctx):
    """[(queue ms, frame ms)] of the window's answered camera messages
    (kept in `ctx`); None where the program numbers no message.

    A message the viewer handed over at `t_sent` (`ctx["steers"]`, on
    `time.perf_counter`'s own scale) waits until a `steer` span that applied
    a camera message drains it (`t_drain`, same scale): the queue part. The
    frame part runs from that span's start to the start of the first `sinks`
    span whose `steer_seq` has reached the span's `seq` (both `ts` of one
    recorder, so their difference needs no epoch). A message drained before
    the window has no span here and is passed over. The two parts are the
    viewer's own answered - sent, and a message where they are not, within
    1 ms, is named on stderr."""
    if "_steer_parts" in ctx:
        return ctx["_steer_parts"]
    drains = sorted((e for e in ctx["spans"] if e["name"] == "steer"
                     and "seq" in (e.get("attrs") or {})),
                    key=lambda e: e["attrs"]["t_drain"])
    sinks = sorted((e for e in ctx["spans"] if e["name"] == "sinks"
                    and (e.get("attrs") or {}).get("steer_seq") is not None),
                   key=lambda e: e["ts"])
    parts = []
    if ctx["spans"] and not (drains and sinks):
        scopes._missing("no `steer` span carries `seq` and `t_drain`, or no "
                        "`sinks` span `steer_seq`: a camera message cannot "
                        "be followed")
    for t_sent, t_answered, _ in (ctx["steers"] if drains and sinks else ()):
        drain = next((d for d in drains
                      if t_sent < d["attrs"]["t_drain"] <= t_answered), None)
        if drain is None:
            continue
        shown = next((s for s in sinks if s["ts"] >= drain["ts"] and
                      s["attrs"]["steer_seq"] >= drain["attrs"]["seq"]), None)
        if shown is None:
            continue
        queue = (drain["attrs"]["t_drain"] - t_sent) * 1e3
        frame = (shown["ts"] - drain["ts"]) * 1e3
        parts.append((queue, frame))
        whole = (t_answered - t_sent) * 1e3
        if abs(queue + frame - whole) > 1.0:
            print(f"[chipbench] steer parts do not add up: message "
                  f"{drain['attrs']['seq']}: queue {queue:.3f} + frame "
                  f"{frame:.3f} ms against the viewer's {whole:.3f} ms",
                  file=sys.stderr, flush=True)
    if parts:
        print(f"[chipbench] steer parts: {len(parts)} of "
              f"{len(ctx['steers'])} answered messages followed from drain "
              f"to sink", file=sys.stderr, flush=True)
    ctx["_steer_parts"] = parts or None
    return ctx["_steer_parts"]


def steer_part_ms(ctx, which: int):
    parts = steer_parts(ctx)
    return None if parts is None else statistics.median(
        p[which] for p in parts)
